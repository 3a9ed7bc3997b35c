"""Device idle per batch while the host is in the program's other spans.

Every ``repro.*`` span but the rung pick: the engine's preparation of a
batch (``engine.prepare``), its dispatch of the index's phases
(``engine_batch``, ``phase_a``, ``phase_b_rerank``, ``merge``), the wait
for the result and its copy to the host (``engine.result_wait``,
``engine.result_fetch``) and its bookkeeping (``engine.record``).  Idle
gaps are put down to the innermost program span over them
(``bench/program_trace.py``).
"""
from bench import program_trace


def read(run, trace):
    return program_trace.idle_ms(
        run, trace, lambda owner: (
            owner.startswith(program_trace.PROGRAM_PREFIX)
            and owner != program_trace.RUNG_PICK))
