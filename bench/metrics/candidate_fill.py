"""Share of phase B's candidate slots that hold a real candidate.

Real candidates are phase A's per-query counts under the per-bucket cap
(``core.index.probe_index``) for the window's queries; the slots are each
batch's rung times its queries (``rung_mean``'s counter).  The rest is
padding that phase B gathers and reranks for nothing.
"""


def read(run, trace):
    rungs = run.get("rungs")
    if not rungs or "candidates" not in run or not run.get("batches"):
        return None
    per_batch = run["attempted"] / run["batches"]
    slots = per_batch * sum(cb * c for cb, c in rungs.items())
    return 100.0 * run["candidates"] / slots
