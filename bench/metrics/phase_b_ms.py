"""Phase B, the rerank (compact gather, dedup, tombstones, exact L1 top-k,
gid map).

Device time per served batch of the program ``segments._finish_segment``,
from the ``XLA Modules`` line of the trace.
"""
PROGRAM = "jit__finish_segment"


def read(run, trace):
    prog = trace and trace["programs"].get(PROGRAM)
    if not prog or not run.get("batches"):
        return None
    return prog["seconds"] / run["batches"] * 1e3
