"""Index build: host clock around the jitted ``core.index.build_index``,
ending when its outputs are ready."""


def read(run, trace):
    return run.get("build_s")
