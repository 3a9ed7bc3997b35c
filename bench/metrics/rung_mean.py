"""The candidate rung the window's batches ran on, averaged over them.

A batch's rung (``core/segments.query_compact``: the smallest rung of the
segment's ladder that holds the batch's largest candidate count) is the
number of candidate slots phase B gathers and reranks for each of its
queries, real or padding; it sets most of phase B's time.  Read from the
engine's ``cand_buckets`` counter, before and after the window.
"""


def read(run, trace):
    rungs = run.get("rungs")
    if not rungs:
        return None
    return sum(cb * c for cb, c in rungs.items()) / sum(rungs.values())
