"""Engine warmup: host clock around the serving index's adoption of the
built state, the engine's construction, which runs
``AnnServingEngine.warmup`` (every candidate rung of the batch shape
compiled and executed once), and one batch served before the window."""


def read(run, trace):
    return run.get("warmup_s")
