"""The DLRM tower stays on the device from its optimizer step to its int8
sync record, on the CPU.

The training plane keeps the tensors its jitted optimizer step returns,
master 0's ``DenseBank`` holds the same device arrays, and the pusher
codes them where they live (``Int8Transform.encode_tensors``), reading
back only the int8 codes, all of a flush's in one blocking read. These
tests hold that path to the host codec's bits, count what crosses
the host boundary in a steady tick, and check that snapshots, restores
and a hot switch still see host numpy.
"""

import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.weips_ctr import DLRM_DCNV2
from repro.core import Int8Transform
from repro.core.ps import DenseBank, MasterShard
from repro.kernels import device_io
from repro.kernels.delta_codec import tensor_rows
from repro.models import ctr
from repro.obs import trace as obs_trace
from repro.optim import FTRL

BENCH = Path(__file__).resolve().parents[1] / "perfbench"
TOWER = ctr.dlrm_shapes(DLRM_DCNV2)


def _load(name, path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def tiny_dlrm():
    """The benchmark's tiny DLRM cut (``perfbench/tests/tiny.py``) on the
    pallas backends, and its family module."""
    for p in (BENCH, BENCH / "tests"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    _load("perfbench_family_dlrm_dcnv2",
          BENCH / "families" / "dlrm_dcnv2.py")
    import tiny
    spec = tiny.spec("dlrm_dcnv2.train_stream")
    spec["cfg"]["cluster"].update(ps_backend="pallas",
                                  codec_backend="pallas")
    return spec


def _cluster(spec, seed):
    """A pre-seeded tiny cluster, its stream and a batch maker."""
    fam, cfg = spec["family"], spec["cfg"]
    cl = fam.build(cfg, seed)
    fam.preseed(cl, cfg, seed, masters=True, replicas=True)
    stream = fam.Stream(cfg, spec["traffic"], seed)
    r = np.random.default_rng(seed % 1000)
    scn = cl.training.scenario()

    def train(b=48):
        ids = stream.slots.sample(r, b, 1.2)
        y = (r.random(b) < 0.25).astype(np.float32)
        x = fam.dense_features(r, b, cfg["dense_features"])
        cl.training.train_batch(scn, ids, y, dense_x=x, bucket=64)

    return cl, scn, train


def _tower_names(cl, scn):
    return [scn.dense_store_name(k) for k in scn.dense]


def _int8_coding(t) -> np.ndarray:
    """What a replica holds of tensor ``t``: its rows int8-coded by the
    host codec, decoded, in ``t``'s shape."""
    t = np.asarray(t)
    return Int8Transform.decode(
        Int8Transform._quantize_np(tensor_rows(t))).reshape(t.shape)


# --------------------------------------------------------------------------
# (a) the device route codes a tensor to the host codec's bits
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", sorted(set(TOWER.values())),
                         ids=lambda s: "x".join(map(str, s)))
def test_device_tensor_codes_to_the_host_routes_bits(shape):
    """Every shape of the published tower: a 13-row weight, the 3,456-row
    cross and top weights, 1-D biases, the one-column last weight. Some
    rows are all zero and magnitudes span six decades."""
    r = np.random.default_rng(sum(shape))
    t = (r.standard_normal(shape) *
         10.0 ** r.integers(-3, 3, shape[:1])[(...,) + (None,) *
                                              (len(shape) - 1)]).astype(
        np.float32)
    if t.ndim > 1:
        t[::7] = 0.0
    pallas = Int8Transform(backend="pallas")
    (dev,), (host,) = (pallas.encode_tensors([a])
                       for a in (jnp.asarray(t), t))
    (numpy,) = Int8Transform().encode_tensors([t])
    rows = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    for enc in (host, numpy):
        assert dev["q"].dtype == np.int8 and dev["q"].shape == (
            rows, shape[-1])
        assert dev["scale"].shape == (rows, 1)
        np.testing.assert_array_equal(dev["q"], enc["q"])
        np.testing.assert_array_equal(dev["scale"], enc["scale"])


def test_mixed_tensors_code_in_one_read():
    """Device and host tensors in one call: each codes to the numpy
    codec's bits, all with one blocking read, only the host tensor counts
    as an upload, and the count of tensors coded on the device goes to
    ``sync.dense_resident``."""
    r = np.random.default_rng(3)
    ts = [r.standard_normal(s).astype(np.float32)
          for s in ((13, 512), (3456,), (20, 7))]
    want = Int8Transform().encode_tensors(ts)
    pallas = Int8Transform(backend="pallas")
    tr = obs_trace.configure(enabled=True)
    try:
        io = device_io.DEVICE_IO
        w0, h0 = io.waits, io.h2d_bytes
        got = pallas.encode_tensors([jnp.asarray(ts[0]), ts[1],
                                     jnp.asarray(ts[2])])
        waits, h2d = io.waits - w0, io.h2d_bytes - h0
        counted = tr.counters.get("sync.dense_resident")
    finally:
        obs_trace.disable()
    assert waits == 1 and counted == 2
    assert h2d == ts[1].nbytes          # at its own shape: no padding
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g["q"], w["q"])
        np.testing.assert_array_equal(g["scale"], w["scale"])


# --------------------------------------------------------------------------
# (b) a steady tick moves none of the tower's f32 bytes
# --------------------------------------------------------------------------

@pytest.fixture
def crossings(monkeypatch):
    """Every array counted across the host boundary, as (direction,
    shape, dtype): ``count_h2d`` and ``to_host`` wrapped wherever a module
    of the program bound them."""
    seen = []
    real_h2d, real_host = device_io.count_h2d, device_io.to_host

    def h2d(*arrays):
        seen.extend(("h2d", a.shape, a.dtype) for a in arrays
                    if isinstance(a, np.ndarray))
        real_h2d(*arrays)

    def host(*outs):
        out = real_host(*outs)
        seen.extend(("d2h", a.shape, a.dtype) for a in out)
        return out

    for mod in list(sys.modules.values()):
        if not getattr(mod, "__name__", "").startswith("repro"):
            continue
        if getattr(mod, "count_h2d", None) is real_h2d:
            monkeypatch.setattr(mod, "count_h2d", h2d)
        if getattr(mod, "to_host", None) is real_host:
            monkeypatch.setattr(mod, "to_host", host)
    return seen


def _tower_like(shape, dtype, tower_shapes) -> bool:
    """An f32 array shaped as a tower tensor, its rows, or its rows padded
    to a power of two."""
    if dtype != np.float32:
        return False
    for s in tower_shapes:
        rows = (int(np.prod(s[:-1])), s[-1]) if len(s) > 1 else (1, s[0])
        pad = (max(8, 1 << (rows[0] - 1).bit_length()), rows[1])
        if shape in (s, rows, pad):
            return True
    return False


def test_steady_tick_moves_no_tower_bytes(tiny_dlrm, crossings):
    cl, scn, train = _cluster(tiny_dlrm, 2 ** 33 + 41)
    for step in range(2):                # the tower goes to the device
        train()
        cl.sync_tick(float(step))
    names = _tower_names(cl, scn)
    bank = cl.masters[0].dense
    assert all(isinstance(bank.tensors[n], jax.Array) for n in names)
    assert all(isinstance(v, jax.Array) for v in scn.dense.values())
    shapes = {tuple(v.shape) for v in scn.dense.values()}

    io = device_io.DEVICE_IO
    crossings.clear()
    h0, d0 = io.h2d_bytes, io.d2h_bytes
    tr = obs_trace.configure(enabled=True)
    try:
        train()
        cl.sync_tick(2.0)
        resident = tr.counters.get("sync.dense_resident")
    finally:
        obs_trace.disable()
    assert resident == len(names) == len(scn.dense)
    # the spy saw every byte the counters counted, and no tower tensor
    # among them either way
    assert sum(int(np.prod(s)) * np.dtype(d).itemsize
               for k, s, d in crossings if k == "h2d") == io.h2d_bytes - h0
    assert sum(int(np.prod(s)) * np.dtype(d).itemsize
               for k, s, d in crossings if k == "d2h") == io.d2h_bytes - d0
    # less the scales of the tower's int8 records, (rows, 1) f32 each
    rest = list(crossings)
    for v in scn.dense.values():
        rows = int(np.prod(v.shape[:-1])) if v.ndim > 1 else 1
        rest.remove(("d2h", (rows, 1), np.dtype(np.float32)))
    tower_like = [c for c in rest if _tower_like(c[1], c[2], shapes)]
    assert not tower_like, tower_like
    # the bank holds the very arrays the next forward reads
    assert all(bank.tensors[scn.dense_store_name(k)] is v
               for k, v in scn.dense.items())

    # the dense push alone: one blocking read of the codes, no upload
    train()
    events = cl.collectors[0].drain()
    dense = {(g, op): ids for g, ids, op in events if g.startswith("dense/")}
    assert len(dense) == len(names)
    h0, d0, w0 = io.h2d_bytes, io.d2h_bytes, io.waits
    produced = cl.pushers[0].push(dense, 3.0)
    assert produced == len(names) * cl.plan.num_slave
    rows = sum(int(np.prod(s[:-1])) if len(s) > 1 else 1
               for s in (tuple(v.shape) for v in scn.dense.values()))
    elems = sum(int(np.prod(v.shape)) for v in scn.dense.values())
    assert io.waits - w0 == 1
    assert io.h2d_bytes == h0
    assert io.d2h_bytes - d0 == elems + 4 * rows       # int8 q, f32 scale


def test_numpy_codec_reads_device_tower_in_one_read(tiny_dlrm):
    """The numpy codec backend takes the tower to the host first, all of
    it in one read, and codes it there; nothing counts as resident."""
    spec = {**tiny_dlrm, "cfg": {**tiny_dlrm["cfg"], "cluster": {
        **tiny_dlrm["cfg"]["cluster"], "codec_backend": "numpy"}}}
    cl, scn, train = _cluster(spec, 2 ** 33 + 43)
    train()
    events = cl.collectors[0].drain()
    dense = {(g, op): ids for g, ids, op in events if g.startswith("dense/")}
    w0 = device_io.DEVICE_IO.waits
    tr = obs_trace.configure(enabled=True)
    try:
        cl.pushers[0].push(dense, 0.0)
        assert "sync.dense_resident" not in tr.counters
    finally:
        obs_trace.disable()
    assert device_io.DEVICE_IO.waits - w0 == 1
    cl.sync_tick(0.0)
    for rep in (r for rs in cl.replica_sets for r in rs.replicas):
        for k, v in scn.dense.items():
            np.testing.assert_array_equal(
                rep.dense[scn.dense_store_name(k)], _int8_coding(v))


# --------------------------------------------------------------------------
# (c) snapshots, restores and a hot switch see host numpy
# --------------------------------------------------------------------------

def test_dense_bank_snapshots_device_tensors_as_numpy():
    """Device tensors and slots come to the host in one blocking read per
    snapshot; a host tensor is copied, never aliased."""
    r = np.random.default_rng(8)
    host = {"w": r.standard_normal((13, 512)).astype(np.float32),
            "b": r.standard_normal(512).astype(np.float32),
            "h": r.standard_normal((4, 3)).astype(np.float32)}
    bank = DenseBank()
    for k, v in host.items():
        if k == "h":
            bank.put(k, v)
        else:
            bank.put(k, jnp.asarray(v), slots={"acc": jnp.asarray(v * v)})
    w0 = device_io.DEVICE_IO.waits
    snaps = (bank.snapshot(), bank.snapshot_delta({"w": 0}))
    assert device_io.DEVICE_IO.waits - w0 == 2
    for snap in snaps:
        for k, t in snap["tensors"].items():
            assert type(t) is np.ndarray
            np.testing.assert_array_equal(t, host[k])
            if k in snap["slots"]:
                acc = snap["slots"][k]["acc"]
                assert type(acc) is np.ndarray
                np.testing.assert_array_equal(acc, host[k] * host[k])
    assert snaps[0]["tensors"]["h"] is not bank.tensors["h"]
    assert set(snaps[0]["slots"]) == {"w", "b"}
    assert set(snaps[1]["tensors"]) == {"w", "b", "h"}
    assert set(bank.snapshot_delta({"w": 1, "b": 1, "h": 1})["tensors"]) \
        == set()
    restored = DenseBank.restore(snaps[0])
    for k, v in host.items():
        assert type(restored.tensors[k]) is np.ndarray
        np.testing.assert_array_equal(restored.tensors[k], v)
    assert restored.versions == bank.versions
    # a master shard's snapshot and reload carry them the same way
    m = MasterShard(0, {"g": 4}, FTRL())
    for k, v in host.items():
        m.push_dense(k, jnp.asarray(v))
    m2 = MasterShard(0, {"g": 4}, FTRL())
    m2.load_snapshot(m.snapshot())
    for k, v in host.items():
        assert type(m2.dense.tensors[k]) is np.ndarray
        np.testing.assert_array_equal(m2.dense.tensors[k], v)


def test_checkpoint_and_hot_switch_with_a_device_tower(tiny_dlrm):
    """A checkpoint of a device tower holds its bits as numpy; a hot
    switch back to it, then the stream's replay, leaves every replica's
    tower at master 0's int8 coding."""
    cl, scn, train = _cluster(tiny_dlrm, 2 ** 33 + 47)
    for step in range(2):
        train()
        cl.sync_tick(float(step))
    names = _tower_names(cl, scn)
    bank = cl.masters[0].dense
    at_ckpt = {n: np.asarray(bank.tensors[n]) for n in names}
    v = cl.checkpoint(2.0)
    dense = cl.store.load(v).shard_snaps[0]["dense"]
    for n in names:
        assert type(dense["tensors"][n]) is np.ndarray
        np.testing.assert_array_equal(dense["tensors"][n], at_ckpt[n])
    train()                                  # past the checkpoint
    cl.sync_tick(3.0)
    cl.downgrader.execute(4.0, version=v)
    cl.sync_tick(4.0)                        # replay from its offsets
    replicas = [r for rs in cl.replica_sets for r in rs.replicas]
    assert replicas
    for rep in replicas:
        for n in names:
            got = rep.dense[n]
            assert type(got) is np.ndarray
            np.testing.assert_array_equal(got, _int8_coding(bank.tensors[n]))
