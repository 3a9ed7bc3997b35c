"""Fixture-only loop: serves the pool's first ``batches`` batches in
order, whatever the window's length."""
import numpy as np


def drive(engine, pool, mix, seconds, span, rng, closed):
    batch, count = int(mix["batch"]), int(mix["batches"])
    sent = pool[:batch * count]
    with span("bench.window"):
        out = [engine.query_batch(q) for q in sent.reshape(count, batch, -1)]
    closed()
    return {"attempted": sent.shape[0], "batches": count,
            "metrics": {"queries_per_s": float(sent.shape[0])},
            "queries": sent, "recall_queries": sent.shape[0],
            "dists": np.concatenate([d for d, _ in out]),
            "ids": np.concatenate([i for _, i in out])}
