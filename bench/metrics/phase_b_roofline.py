"""Phase B's share of its roofline, which HBM bandwidth bounds.

Phase B does no arithmetic worth counting against the chip's operation
peak: for each candidate it reads one id and one row and takes an L1
distance (a subtract, an absolute value and an add per coordinate, on the
vector unit).  So its least time is the bytes it has to move over the
chip's HBM bandwidth, and the share is that least time over the device
time of the ``segments._finish_segment`` program in the window.

The bytes count real candidates only (phase A's per-query counts under the
per-bucket cap, as ``core.index.probe_index`` reports them for the window's
queries), not the padded rung the program gathers: a program that gathers
padding moves bytes that buy nothing, and that shows here as a lower share.
Those counts are the program's own, so a change to how ``probe_index``
counts or caps candidates moves this share without any change in phase B.
"""
PROGRAM = "jit__finish_segment"
ID_BYTES = 4
QUERY_ITEMSIZE = 4


def phase_b_bytes(candidates: int, queries: int, dim: int,
                  itemsize: int) -> int:
    """HBM bytes phase B has to move for ``candidates`` real candidates of
    ``queries`` queries: each candidate's id and row, and each query."""
    return (candidates * (dim * itemsize + ID_BYTES)
            + queries * dim * QUERY_ITEMSIZE)


def read(run, trace):
    prog = trace and trace["programs"].get(PROGRAM)
    if not prog or not prog["seconds"] or "candidates" not in run:
        return None
    moved = phase_b_bytes(run["candidates"], run["attempted"], run["dim"],
                          run["itemsize"])
    least_s = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / prog["seconds"]
