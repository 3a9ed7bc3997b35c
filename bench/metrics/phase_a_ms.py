"""Phase A, the probe (hash, probe keys, bucket extents, candidate counts).

Device time per served batch of the program ``core.index.probe_index``
(``segments._probe_segment``), from the ``XLA Modules`` line of the trace.
"""
PROGRAM = "jit_probe_index"


def read(run, trace):
    prog = trace and trace["programs"].get(PROGRAM)
    if not prog or not run.get("batches"):
        return None
    return prog["seconds"] / run["batches"] * 1e3
