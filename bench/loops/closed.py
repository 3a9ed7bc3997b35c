"""Closed loop: one client sends a batch, waits for its answer, sends the
next.

A mix that names ``"loop": "closed"`` gives:

* ``batch``: queries per request;
* ``recall_queries``: how many of the first answered queries the recall
  is taken over (served after the window too, where it closes sooner).

The queries are the cell's whole pool, sent in passes: each pass is an
order of the pool drawn from the run's seed, and batches are cut from the
stream of passes, so a run cycles the pool as often as it needs.  The
program's work for a batch follows from which queries it holds (its
largest candidate count picks its candidate rung), and the window takes
the mix of rungs that the pool gives.

The window opens when the first batch is formed and closes at the first
batch completion at or after ``seconds``, so a batch is never cut in two;
``closed()`` is called right there.  Host spans
(``jax.profiler.TraceAnnotation``) name what the client is doing, so that a
trace can put each idle gap of the device down to it.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["drive"]


def drive(engine, pool: np.ndarray, mix: dict, seconds: float, span,
          rng: np.random.Generator, closed) -> dict:
    """Run the window; return what was served and what it measured.

    ``engine.query_batch`` serves a batch, ``span(name)`` is a context
    manager for a host span and ``rng`` draws the order.  The result has
    ``attempted`` queries and ``batches`` in the window, its end-to-end
    ``metrics``, and every answered query (the window's first, in order)
    with its ``dists`` and ``ids``, of which the first ``recall_queries``
    make the recall's set.
    """
    batch = int(mix["batch"])
    recall = int(mix["recall_queries"])
    stream = np.zeros(0, np.int64)

    def next_rows(pos):
        nonlocal stream
        while stream.size < pos + batch:
            stream = np.concatenate([stream,
                                     rng.permutation(pool.shape[0])])
        return stream[pos:pos + batch]

    dists, ids = [], []
    pos = 0
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            with span("bench.form_batch"):
                sent = pool[next_rows(pos)]
            with span("bench.query_batch"):
                d, i = engine.query_batch(sent)
            with span("bench.handle_result"):
                dists.append(d)
                ids.append(i)
            pos += batch
            t_end = time.perf_counter()
            if t_end - t0 >= seconds:
                break
    closed()
    in_window = pos
    while pos < recall:                 # a short window: finish the set
        d, i = engine.query_batch(pool[next_rows(pos)])
        dists.append(d)
        ids.append(i)
        pos += batch
    return {"attempted": in_window, "batches": in_window // batch,
            "metrics": {"queries_per_s": in_window / (t_end - t0)},
            "queries": pool[stream[:pos]], "recall_queries": recall,
            "dists": np.concatenate(dists), "ids": np.concatenate(ids)}
