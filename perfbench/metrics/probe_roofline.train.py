"""Share (%) of the hash-map probe's roofline in the train path: the
least time of probing every train batch's unique ids once per group
(the fused FTRL chain probes them; the pull of master rows is a host
gather; ``counts.probe``, bytes-bound) over the device time of the probe kernel in the trace: the
Pallas custom calls whose outputs are the probe's lane-dense per-id
state, ``s32[n,1,128]`` blocks (one call per probe pass)."""

OP = r"^%\S+ = \(s32\[\d+,1,128\].*\) custom-call\("


def read(ctx):
    r, pk = ctx.trace, ctx.peaks
    if r is None or pk is None or not ctx.unique_per_batch:
        return None
    t = r.op_ns(OP) * 1e-9
    if t <= 0:
        return None
    c = ctx.counts
    load = c.map_load(ctx.cfg["sizing"]["ids_per_master"])
    nbytes = 0.0
    for u in ctx.unique_per_batch:
        for g in ctx.cfg["groups"]:
            nbytes += c.probe(u[g], load)[1]
    return 100.0 * c.least_time(0.0, nbytes, pk)[0] / t
