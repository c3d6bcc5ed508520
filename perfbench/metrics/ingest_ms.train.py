"""Mean milliseconds per tick in ``TrainPipeline.ingest`` (the join's
exposure and feedback offers), a harness span."""


def read(ctx):
    t = ctx.spans.get("ingest")
    if not t:
        return None
    return sum(b - a for a, b in t) / len(t) * 1e3
