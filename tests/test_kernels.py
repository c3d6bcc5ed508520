"""Per-kernel shape/dtype sweeps vs. the pure-jnp oracles in ref.py
(assignment requirement). Kernels run in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                       # hypothesis is a dev extra; the container may
    from hypothesis import given, settings        # not have it — fall back
    from hypothesis import strategies as st       # to fixed examples.
except ModuleNotFoundError:
    given = settings = st = None

from repro.core import Int8Transform
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("b,d", [(8, 128), (300, 256), (1, 512)])
@pytest.mark.parametrize("params", [
    dict(alpha=0.05, beta=1.0, l1=1.0, l2=1.0),
    dict(alpha=0.1, beta=0.5, l1=0.0, l2=0.1),
])
def test_ftrl_sweep(b, d, params):
    ks = jax.random.split(jax.random.fold_in(KEY, b * d), 3)
    z = jax.random.normal(ks[0], (b, d)) * 2
    n = jax.random.uniform(ks[1], (b, d)) * 4
    g = jax.random.normal(ks[2], (b, d))
    got = ops.ftrl_row_update(z, n, g, **params)
    want = ref.ftrl_row_update(z, n, g, **params)
    for a, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("b,d", [(4, 128), (100, 256), (1, 1024)])
def test_codec_sweep(b, d):
    x = jax.random.normal(jax.random.fold_in(KEY, b + d), (b, d)) * 10
    q, s = ops.quantize_rows(x)
    qr, sr = ref.quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    got = Int8Transform.decode({"q": q, "scale": s})
    np.testing.assert_allclose(np.asarray(got), np.asarray(x),
                               atol=float(np.abs(x).max()) / 120)


def _scale_cases(fn):
    if st is not None:
        return settings(max_examples=30, deadline=None)(
            given(st.floats(-1e4, 1e4, width=32))(fn))
    return pytest.mark.parametrize(
        "scale", [0.0, 1.0, -3.5, 127.0, -511.25, 1e4])(fn)


@_scale_cases
def test_codec_roundtrip_error_property(scale):
    x = jnp.asarray(np.linspace(-abs(scale) - 1, abs(scale) + 1, 256,
                                dtype=np.float32)).reshape(1, 256)
    q, s = ops.quantize_rows(x)
    back = Int8Transform.decode({"q": q, "scale": s})
    step = float(np.abs(x).max()) / 127.0
    assert float(np.abs(np.asarray(back) - np.asarray(x)).max()) <= \
        step / 2 + 1e-5


@pytest.mark.parametrize("b,h,g,s,d", [
    (1, 4, 2, 128, 128),       # GQA 2:1
    (2, 4, 4, 256, 128),       # MHA
    (1, 8, 1, 128, 256),       # MQA, bigger head
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, h, g, s, d, causal, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, b * h * s), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, g, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, g, s, d), jnp.float32).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,h,g,s,d,block", [
    (2, 8, 2, 1024, 128, 512),
    (1, 4, 4, 512, 128, 128),
    (3, 2, 1, 2048, 256, 512),
])
def test_decode_attention_sweep(b, h, g, s, d, block):
    ks = jax.random.split(jax.random.fold_in(KEY, b * h + s), 4)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, g, d))
    v = jax.random.normal(ks[2], (b, s, g, d))
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    got = ops.decode_attention(q, k, v, lengths, block_k=block)
    want = ref.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_short_lengths():
    """Valid-length masking: only the first `len` cache slots count."""
    b, h, g, s, d = 1, 2, 1, 512, 128
    q = jax.random.normal(KEY, (b, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, g, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, g, d))
    # poison the tail: results must not change
    k_poison = k.at[:, 10:].set(1e6)
    v_poison = v.at[:, 10:].set(1e6)
    lengths = jnp.array([10], jnp.int32)
    a = ops.decode_attention(q, k, v, lengths)
    bb = ops.decode_attention(q, k_poison, v_poison, lengths)
    np.testing.assert_allclose(np.asarray(a), np.asarray(bb), rtol=1e-6)


@pytest.mark.parametrize("v,d,n", [(64, 128, 16), (128, 256, 64)])
def test_embedding_scatter_sweep(v, d, n):
    """Set-scatter (unique ids contract) into a donated table: rows named
    by ids are replaced, every other row is untouched."""
    table = jax.random.normal(KEY, (v, d))
    ids = jax.random.permutation(jax.random.fold_in(KEY, 4),
                                 jnp.arange(v))[:n]
    upd = jax.random.normal(jax.random.fold_in(KEY, 5), (n, d))
    want = ref.embedding_scatter(table, ids, upd)
    got = ops.embedding_scatter(table, ids.astype(jnp.int32), upd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _probe_case(cap_pow, n_ids, n_del, seed):
    """Build a host map with live keys, tombstones, and a grown capacity;
    return it plus a probe batch mixing hits / misses / deleted ids /
    sentinel-valued queries."""
    from repro.core.hashmap import EMPTY, TOMB, IdHashMap
    rng = np.random.default_rng(seed)
    m = IdHashMap(16)                      # grows through every boundary
    ids = rng.choice(1 << 40, size=n_ids, replace=False).astype(np.int64)
    m.put(ids, np.arange(n_ids))
    dele = ids[:n_del]
    if n_del:
        m.delete(dele)
    assert m.capacity == 1 << cap_pow      # the size the sweep intends
    absent = rng.choice(1 << 40, size=64, replace=False).astype(np.int64)
    absent = absent[~np.isin(absent, ids)]
    qs = np.concatenate([
        ids[n_del:], dele, absent,
        np.array([int(EMPTY), int(TOMB), 0, -1], np.int64)])
    return m, qs


@pytest.mark.parametrize("cap_pow,n_ids,n_del", [
    (8, 60, 10),           # one windowed-tail round typical
    (12, 1000, 200),       # grown map, heavier tombstone load
    (14, 4000, 0),         # capacity boundary: exactly at 25% load trigger
])
def test_hashmap_probe_matches_host_map(cap_pow, n_ids, n_del):
    """Device probe (uint32-limb Fibonacci hash, windowed while_loop) is
    bit-equal to ``IdHashMap._probe`` on its own key table: same found
    mask, same position wherever found. Misses, tombstoned ids, and the
    two reserved sentinel values all resolve identically."""
    m, qs = _probe_case(cap_pow, n_ids, n_del, seed=cap_pow)
    host_pos, host_found = m._probe(qs)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    pos, found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                   shift=int(m.shift))
    pos, found = np.asarray(pos), np.asarray(found)
    np.testing.assert_array_equal(found, host_found)
    np.testing.assert_array_equal(pos[found], host_pos[host_found])
    # found positions hold exactly the queried ids
    np.testing.assert_array_equal(m.key_table[pos[found]], qs[found])


@pytest.mark.parametrize("cap_pow,n_ids,n_del", [(8, 60, 10),
                                                 (12, 1000, 200)])
def test_hashmap_probe_ref_oracle_matches_kernel(cap_pow, n_ids, n_del):
    """The brute-force ref oracle (full circular probe order, window-index
    binning) and the Pallas kernel agree everywhere — including the pos
    column at found rows (pos is unspecified where found is False)."""
    m, qs = _probe_case(cap_pow, n_ids, n_del, seed=100 + cap_pow)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    got_pos, got_found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                           shift=int(m.shift))
    ref_pos, ref_found = ref.hashmap_probe(klo, khi, qlo, qhi,
                                           shift=int(m.shift))
    got_found, ref_found = np.asarray(got_found), np.asarray(ref_found)
    np.testing.assert_array_equal(got_found, ref_found)
    np.testing.assert_array_equal(np.asarray(got_pos)[got_found],
                                  np.asarray(ref_pos)[ref_found])


def test_public_kernel_entrypoints_documented():
    """Every public symbol in the kernel modules carries a docstring that
    states its contract (KERNELS.md companion check)."""
    import inspect

    from repro.kernels import delta_codec, ftrl_row_update, hashmap_probe
    for mod in (delta_codec, ftrl_row_update, hashmap_probe, ops, ref):
        assert (mod.__doc__ or "").strip(), mod.__name__
        for name, fn in vars(mod).items():
            if name.startswith("_") or not inspect.isfunction(fn):
                continue
            if fn.__module__ != mod.__name__:
                continue                    # re-exported helpers
            doc = (inspect.getdoc(fn) or "").strip()
            assert len(doc) >= 20, f"{mod.__name__}.{name} undocumented"


# -- HBM-resident probe: windowed DMA + double-buffered VMEM scratch --------
# The VMEM placement holds the whole key table in VMEM, which caps map
# capacity at VMEM_SLOT_BOUND. The HBM placement keeps the limbs in HBM
# and DMAs each id's probe window into scratch — these tests pin it
# bit-equal to the host map and the ref oracle across capacity edges,
# tombstone walks, grown maps, and probe chains that cross window
# boundaries (forced via tiny windows + crafted hash collisions).

@pytest.mark.parametrize("cap_pow,n_ids,n_del", [
    (4, 3, 1),             # capacity edge: cap 16 << DMA window (wrap pad)
    (8, 60, 10),           # one windowed-tail round typical
    (12, 1000, 200),       # grown map, heavier tombstone load
    (14, 4000, 0),         # capacity boundary: exactly at 25% load trigger
])
def test_hashmap_probe_hbm_matches_host_map(cap_pow, n_ids, n_del):
    """Forced ``placement="hbm"`` probe is bit-equal to ``IdHashMap._probe``
    on the same table — found mask, positions, sentinels, tombstones —
    even when the map is far smaller than one DMA window (wrap pad)."""
    m, qs = _probe_case(cap_pow, n_ids, n_del, seed=7 + cap_pow)
    host_pos, host_found = m._probe(qs)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    pos, found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                   shift=int(m.shift), placement="hbm")
    pos, found = np.asarray(pos), np.asarray(found)
    np.testing.assert_array_equal(found, host_found)
    np.testing.assert_array_equal(pos[found], host_pos[host_found])
    np.testing.assert_array_equal(m.key_table[pos[found]], qs[found])


@pytest.mark.parametrize("cap_pow,n_ids,n_del", [(8, 60, 10),
                                                 (12, 1000, 200)])
def test_hashmap_probe_hbm_matches_vmem_and_ref(cap_pow, n_ids, n_del):
    """Triple agreement: HBM windowed-DMA kernel == VMEM streaming kernel
    == brute-force ref oracle, including pos at found rows."""
    m, qs = _probe_case(cap_pow, n_ids, n_del, seed=300 + cap_pow)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    h_pos, h_found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                       shift=int(m.shift), placement="hbm")
    v_pos, v_found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                       shift=int(m.shift), placement="vmem")
    r_pos, r_found = ref.hashmap_probe(klo, khi, qlo, qhi,
                                       shift=int(m.shift))
    h_found = np.asarray(h_found)
    np.testing.assert_array_equal(h_found, np.asarray(v_found))
    np.testing.assert_array_equal(h_found, np.asarray(r_found))
    np.testing.assert_array_equal(np.asarray(h_pos)[h_found],
                                  np.asarray(v_pos)[h_found])
    np.testing.assert_array_equal(np.asarray(h_pos)[h_found],
                                  np.asarray(r_pos)[h_found])


@pytest.mark.parametrize("window,chunk", [(16, 8), (32, 16)])
def test_hashmap_probe_hbm_window_boundary_chains(window, chunk):
    """Probe chains LONGER than one DMA window: ids crafted to share a
    home-slot neighbourhood pile into one collision cluster, so resolving
    them needs continuation passes (window i exhausted → DMA window i+1).
    Tiny windows make every cluster cross a boundary; still bit-equal."""
    from repro.core.hashmap import IdHashMap, home_slots
    from repro.kernels.hashmap_probe import hashmap_probe
    rng = np.random.default_rng(5)
    m = IdHashMap(1024)
    cand = rng.choice(1 << 40, size=200_000, replace=False).astype(np.int64)
    homes = home_slots(cand, m.shift)
    cluster = cand[(homes >= 100) & (homes < 104)][:48]   # one long chain
    assert len(cluster) >= 40
    spread = cand[homes % 7 == 0][:120]
    ids = np.unique(np.concatenate([cluster, spread]))
    m.put(ids, np.arange(len(ids)))
    assert m.capacity == 1024                  # load stays under 25%
    absent = cand[~np.isin(cand, ids)][:64]
    qs = np.concatenate([cluster, absent])
    host_pos, host_found = m._probe(qs)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    pos, found = hashmap_probe(klo, khi, qlo, qhi, shift=int(m.shift),
                               placement="hbm", interpret=True,
                               window=window, chunk=chunk)
    pos, found = np.asarray(pos), np.asarray(found)
    np.testing.assert_array_equal(found, host_found)
    np.testing.assert_array_equal(pos[found], host_pos[host_found])


def test_hashmap_probe_hbm_past_vmem_bound():
    """A 4M-slot map — past VMEM_SLOT_BOUND, where auto placement flips to
    "hbm" and the old streaming kernel could not run at all. Lookup via
    the public auto path stays bit-equal to the host map."""
    from repro.core.hashmap import IdHashMap
    from repro.kernels.hashmap_probe import VMEM_SLOT_BOUND
    rng = np.random.default_rng(9)
    m = IdHashMap(1 << 22)
    assert m.capacity > VMEM_SLOT_BOUND
    ids = np.unique(rng.integers(1, 1 << 62, size=4096).astype(np.int64))
    m.put(ids, np.arange(len(ids)))
    m.delete(ids[::5])
    qs = np.concatenate([ids, ids[::5],
                         rng.integers(1 << 62, (1 << 63) - 1,
                                      size=256).astype(np.int64)])
    host_pos, host_found = m._probe(qs)
    klo, khi = ops.int64_limbs(m.key_table)
    qlo, qhi = ops.int64_limbs(qs)
    pos, found = ops.hashmap_probe(klo, khi, qlo, qhi, shift=int(m.shift))
    pos, found = np.asarray(pos), np.asarray(found)
    np.testing.assert_array_equal(found, host_found)
    np.testing.assert_array_equal(pos[found], host_pos[host_found])


def test_fused_lookup_found_mask_and_slots():
    """``fused_lookup``'s third output: arena slots at found rows (the
    LRU-touch signal ``ServeCache.lookup_device`` consumes) and 0 at
    misses; rows at misses are zeros; mask matches the host map."""
    from repro.core.ps import SparseTable
    rng = np.random.default_rng(3)
    st = SparseTable(8, ("n", "z"), backend="pallas")
    ids = np.unique(rng.integers(1, 1 << 40, size=512).astype(np.int64))
    st.ensure(ids)
    absent = rng.integers(1 << 41, 1 << 42, size=64).astype(np.int64)
    qs = np.concatenate([ids[:128], absent])
    rows, found, slot = st.lookup_device(qs)
    rows = np.asarray(rows)
    assert found[:128].all() and not found[128:].any()
    np.testing.assert_array_equal(slot[found], st.lookup(qs)[found])
    assert (slot[~found] == 0).all()
    np.testing.assert_array_equal(rows[~found], 0.0)
    np.testing.assert_array_equal(rows[found],
                                  st._w[st.lookup(qs)[found]])
