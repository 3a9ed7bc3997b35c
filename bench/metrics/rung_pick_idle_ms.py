"""Device idle per batch while the host picks the candidate rung.

The ``repro.rung_pick`` span (``core/segments.query_compact``) covers the
one host read of a batch: it waits for phase A, runs ``counts.max()`` and
copies it back, then ``pipeline.pick_rung`` picks the rung phase B is
compiled for.  Idle gaps of the device in the window are put down to the
innermost program span over them (``bench/program_trace.py``).
"""
from bench import program_trace


def read(run, trace):
    return program_trace.idle_ms(
        run, trace, lambda owner: owner == program_trace.RUNG_PICK)
