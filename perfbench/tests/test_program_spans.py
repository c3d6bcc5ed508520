"""The program's spans in a profiler trace (``harness/spans.py``): self
time, idle gaps put down to the innermost span, harness-span cover; the
staged readers on stand-in contexts; a traced run with the program's
spans on at a tiny size on the CPU; and the probe kernel's name against
the pattern ``probe_roofline.train`` reads."""

import re
from types import SimpleNamespace as NS

import pytest

import tiny
from harness import bench, spans
from tools import spans as tool


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes(apply_end=2000):
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        ev("bench.window", 1000, 1000),
        ev("bench.train_tick", 1000, 600),
        ev("bench.apply", 1600, apply_end - 1600),
        ev("train.batch", 1050, 500),
        ev("train.dedup", 1100, 100),
        ev("ps.apply", 1250, 250),
        ev("device.wait", 1300, 100),
        ev("sync.apply", 1650, 250),
        ev("sync.decode", 1700, 50),
        ev("unrelated", 0, 5000),
        ev("train.batch", 2100, 50),                 # after the window
    ])])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("%fusion", 1300, 100), ev("%fusion.1", 1950, 30)])])
    return [host, dev]


def test_self_time_takes_out_nested_spans():
    p = spans.reduce_planes(planes())
    assert p.self_ns == {"train.batch": 150.0, "train.dedup": 100.0,
                         "ps.apply": 150.0, "device.wait": 100.0,
                         "sync.apply": 200.0, "sync.decode": 50.0}
    assert p.count["train.batch"] == 1            # one in the window
    assert p.self_ms(prefixes=("train.",)) == pytest.approx(250e-6)
    assert p.self_ms(names=("device.wait",)) == pytest.approx(100e-6)
    assert p.wait_under == {"ps.apply": 100.0}


def test_idle_goes_to_innermost_span_then_harness_span():
    p = spans.reduce_planes(planes())
    # gaps [1000,1300], [1400,1950], [1980,2000]
    assert p.idle_ns == {"bench.train_tick": 100.0, "train.batch": 150.0,
                         "train.dedup": 100.0, "ps.apply": 150.0,
                         "bench.apply": 120.0, "sync.apply": 200.0,
                         "sync.decode": 50.0}
    assert p.idle_total_ns == 870.0
    assert p.idle_program_share == pytest.approx(650 / 870)
    assert p.cover == {"bench.train_tick": pytest.approx(500 / 600),
                       "bench.apply": pytest.approx(250 / 400)}
    b = p.breakdown()
    assert b["self_s"][0] == ["sync.apply", pytest.approx(2e-7), 1]
    assert b["idle_gaps"][0] == ["sync.apply", pytest.approx(2e-7)]


def test_idle_under_no_span_is_other():
    p = spans.reduce_planes(planes(apply_end=1980))
    assert p.idle_ns["other"] == 20.0
    assert p.idle_ns["bench.apply"] == 100.0


def test_flatten_cuts_a_span_that_outlives_its_parent():
    assert spans.flatten([("a", 0, 10), ("b", 5, 15), ("c", 20, 30)]) == \
        [(0, 5, "a"), (5, 10, "b"), (20, 30, "c")]
    # two threads' stretches: the later one keeps what the earlier leaves
    assert spans.disjoint([(0, 10, "x"), (5, 20, "y")]) == \
        [(0, 10, "x"), (10, 20, "y")]


def test_no_window_span_is_an_error():
    p = planes()
    p[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        spans.reduce_planes(p)


def ctx(program=None, io=None, ticks=4, examples=100):
    trace = None if program is False else NS(
        **({} if program is None else {"program_spans": program}))
    stats = {"ticks": ticks, "examples": examples}
    if io is not None:
        stats["device_io"] = io
    return NS(trace=trace, stats=stats)


# self milliseconds a tick of the stand-in's spans over 4 ticks
READS = {"train_host_ms.train": 8e-3 / 4,        # train.batch + drain
         "ps_host_ms.train": 4e-3 / 4,
         "device_wait_ms.train": 2e-3 / 4,
         "replica_host_ms.train": 7e-3 / 4}      # not sync.push


@pytest.mark.parametrize("metric", sorted(READS))
def test_span_readers(metric):
    p = spans.ProgramSpans(self_ns={
        "train.batch": 5000.0, "train.drain": 3000.0, "ps.ftrl": 4000.0,
        "device.wait": 2000.0, "sync.apply": 3000.0, "sync.decode": 1000.0,
        "cache.invalidate": 3000.0, "sync.push": 9000.0})
    read = bench.reader(metric)
    assert read(ctx(p)) == pytest.approx(READS[metric])
    # no trace, a trace without the program's spans, none of the names
    assert read(ctx(False)) is None
    assert read(ctx()) is None
    assert read(ctx(spans.ProgramSpans(self_ns={"serve.flush": 1.0}))) \
        is None
    assert read(ctx(p, ticks=0)) is None


@pytest.mark.parametrize("metric,key", [
    ("h2d_bytes_per_example.train", "h2d_bytes"),
    ("d2h_bytes_per_example.train", "d2h_bytes")])
def test_counter_readers(metric, key):
    read = bench.reader(metric)
    io = {"h2d_bytes": 3000, "d2h_bytes": 5000, "waits": 7}
    assert read(ctx(io=io)) == io[key] / 100
    assert read(ctx()) is None                     # counters not read
    assert read(ctx(io=io, examples=0)) is None


def test_staged_metrics_have_readers_and_are_unlisted():
    listed = {m["name"] for m in bench.load_json(
        bench.ROOT / "BENCHMARK.json")["per_layer"]}
    for m in tool.STAGED:
        assert m["name"] not in listed
        assert callable(bench.reader(m["name"]))


def test_traced_run_with_program_spans():
    """The tool's run at a tiny size: every staged metric reads, every
    listed one still does, and program spans cover the train tick."""
    spec = tool.staged(tiny.spec("fm_ftrl.train_stream"))
    with tool.Hooks() as hooks:
        r = tool.run(spec, 2 ** 33 + 17, 1.5, hooks, allow_cpu=True,
                     log=lambda *a, **k: None)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for s in tool.STAGED:
        assert m[s["name"]]["value"] > 0, s["name"]
    for name in ("train_tick_ms.train", "apply_ms.train", "push_ms.train"):
        assert name in m
    own = {n for n, _, _ in r["program"]["self_s"]}
    assert {"ps.ftrl", "device.wait", "sync.encode"} <= own
    assert r["program"]["cover"]["bench.train_tick"] > 0.9
    wait_under_s = set(r["program"]["wait_under_s"])
    assert {"ps.ftrl", "sync.encode"} <= wait_under_s
    # the replicas decode int8 records on the host: no blocking read
    assert "sync.decode" not in wait_under_s
    assert r["end_to_end"]["train_examples_per_s"] > 0
    assert r["end_to_end"]["staleness_p95_ms"] > 0
    # the hooks are gone once the tool's run is over
    from harness import drive
    assert drive.TrainDriver.window.__name__ == "window"


def test_probe_keeps_the_name_the_roofline_pattern_finds():
    """The probe kernel, named by its ``pallas_call``, is still the op
    ``probe_roofline.train`` reads: compiled for a described v5e (no
    chip), its instruction text matches the reader's pattern."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import hashmap_probe as hm
    from repro.kernels import ops
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e topology can be described here: {e}")
    sh = SingleDeviceSharding(topo.devices[0])
    cap = 1 << 12
    lo, _ = jax.eval_shape(lambda k: hm.wrap_pad_limbs(k, k, cap=cap),
                           jax.ShapeDtypeStruct((cap,), jnp.uint32))
    k = jax.ShapeDtypeStruct(lo.shape, jnp.uint32, sharding=sh)
    slot = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=sh)
    ids = jax.ShapeDtypeStruct((1024,), jnp.uint32, sharding=sh)
    arena = jax.ShapeDtypeStruct((1 << 14, 8), jnp.float32, sharding=sh)
    grads = jax.ShapeDtypeStruct((1024, 8), jnp.float32, sharding=sh)
    interp = ops._interpret
    ops._interpret = lambda: False
    try:
        text = ops._ftrl_program.lower(
            k, k, slot, arena, arena, arena, ids, ids, grads,
            shift=64 - cap.bit_length() + 1, alpha=0.05, beta=1.0, l1=1.0,
            l2=1.0).compile().as_text()
    finally:
        ops._interpret = interp
    op = bench.reader("probe_roofline.train").__globals__["OP"]
    insts = [re.sub(r"^\s*(ROOT )?", "", ln) for ln in text.splitlines()]
    found = [i for i in insts if re.search(op, i)]
    assert len(found) == 1 and found[0].startswith("%hashmap_probe")
