"""Device idle per batch while the host is outside every program span.

The client's own spans (``bench.form_batch``, ``bench.handle_result``, the
part of ``bench.query_batch`` outside the engine's spans, and the window
between them).  With ``rung_pick_idle_ms`` and ``engine_idle_ms`` it adds
up to the window's idle time over its batches (``bench/program_trace.py``).
"""
from bench import program_trace


def read(run, trace):
    return program_trace.idle_ms(
        run, trace, lambda owner: not owner.startswith(
            program_trace.PROGRAM_PREFIX))
