"""The growth loop changes the carried histogram cache by two slice writes
per split and by nothing else (ops/grow_partition.py, scopes
lgbm.grow.cache and lgbm.grow.carry).

Two halves.  `test_tree_is_the_parents`: the trees of fixed small
problems are, bit for bit, the ones the code grew when the cache was
masked whole after every split (PR 27's tree, commit f03d3fc): the
fingerprints below were taken there and only a change of the arithmetic
may move them.  The problems cover every way the body runs with
`no_split` (growth ends for want of gain, the bump allocator is full, a
forced entry is invalid), where the two writes put back what was there.
The inputs are dyadic rationals, so every sum is exact in float32 under
any association and the fingerprints do not depend on how a backend
orders a reduction.  To take them again, run this file's tests with
EXPECTED emptied: each failure shows the fingerprint that was grown.

The structure tests read the growth program: in the jaxpr of the loop's
body nothing but `dynamic_update_slice` may produce a value of the
cache's shape; and in the HLO that XLA compiles from it, for this backend
and for a described TPU v5e (the chip's own compiler, with the Mosaic
kernels, no chip needed), no copy or select of the cache's type may stand
in a `while` body, which is how a whole-cache copy per split shows in a
device trace.  All three fail on the body as it was.

Small shapes, one arena tile: the Pallas kernels run in interpret mode
(the v5e compile alone lowers them through Mosaic).
"""
import functools
import hashlib
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import grow_partition as gp
from lightgbm_tpu.ops import partition_pallas as pp
from lightgbm_tpu.ops import quantize as qz
from lightgbm_tpu.ops.split import SplitParams

F, B = 5, 16


def _problem(n, seed=42):
    """Bins, gradients and hessians whose every partial sum is exact in
    float32: gradients in sixteenths, hessians in halves."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, (n, F)).astype(np.uint8)
    signal = (bins[:, 0].astype(np.float32) - 7.5) / 4 \
        + (bins[:, 2] > 9) * 0.75
    grad = np.round((signal + rng.randn(n)) * 16) / 16
    hess = rng.randint(1, 4, n) / 2
    return bins, grad.astype(np.float32), hess.astype(np.float32)


def _forced_problem():
    """Feature 1 is constant, so a forced entry on it is invalid and runs
    the body with no_split while growth goes on afterwards, from the
    cache: an entry not put back would change the tree."""
    n = 256
    bins = np.zeros((n, F), np.uint8)
    bins[:, 0] = np.arange(n) % 16
    bins[:, 1] = 9
    bins[:, 2] = np.where(np.arange(n) % 2 == 0, 3, 12)
    bins[:, 3:] = np.random.RandomState(3).randint(0, B, (n, F - 3))
    noise = np.round(np.random.RandomState(7).randn(n) * 4) / 16
    grad = ((bins[:, 0].astype(np.float32) - 7.5) / 4 + noise)
    return bins, grad.astype(np.float32), np.ones(n, np.float32)


def _args(bins, grad, hess, cap_tiles=8):
    n = len(grad)
    return (jnp.zeros((pp.arena_channels(F), cap_tiles * pp.TILE),
                      pp.ARENA_DT),
            jnp.asarray(bins.T.astype(np.float32)), jnp.asarray(grad),
            jnp.asarray(hess), jnp.zeros(n, jnp.int32), jnp.ones(F, bool),
            jnp.full(F, B, jnp.int32), jnp.zeros(F, jnp.int32),
            jnp.zeros(F, jnp.int32))


def _fingerprint(tree, leaf_ids, truncated):
    """(leaves, truncated, sha256 over every field of the tree and the
    rows' leaf ids, bytes as they are)."""
    h = hashlib.sha256()
    for name in tree._fields:
        a = np.ascontiguousarray(np.asarray(getattr(tree, name)))
        h.update(("%s:%s:%s;" % (name, a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    h.update(np.ascontiguousarray(np.asarray(leaf_ids, np.int32)).tobytes())
    return int(tree.num_leaves), bool(truncated), h.hexdigest()[:24]


def _serial(problem, params, cap_tiles=8, **kw):
    tree, leaf_ids, _, truncated = gp.grow_tree_partition(
        *_args(*problem, cap_tiles=cap_tiles), params, max_bin=B,
        interpret=True, **kw)
    return _fingerprint(tree, leaf_ids, truncated)


def _data_parallel(problem, params, devices=4, **kw):
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.parallel.collective import AXIS, shard_mapped
    bins, grad, hess = problem
    n_loc = len(grad) // devices
    C, cap_loc = pp.arena_geometry(n_loc, F)
    _, bins_t, g, h, r0, fm, nb, db, mt = _args(bins, grad, hess)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:devices]), (AXIS,))

    def shard_fn(bins_t, g, h, r0):
        tree, leaf_ids, _, truncated = gp.grow_tree_partition_impl(
            jnp.zeros((C, cap_loc), pp.ARENA_DT), bins_t, g, h, r0, fm, nb,
            db, mt, params, max_bin=B, interpret=True, axis_name=AXIS,
            learner="data", num_machines=devices, full_bag=True, **kw)
        return tree, leaf_ids, truncated

    tree, leaf_ids, truncated = jax.jit(shard_mapped(
        shard_fn, mesh, (P(None, AXIS), P(AXIS), P(AXIS), P(AXIS)),
        (P(), P(AXIS), P())))(bins_t, g, h, r0)
    return _fingerprint(tree, leaf_ids, truncated)


def _quantized(problem, params, **kw):
    bins, grad, hess = problem
    g_code, h_code, gs, hs = qz.quantize_gradients(
        grad, hess, qz.quantize_key(7, 0))
    return _serial((bins, np.asarray(g_code), np.asarray(h_code)), params,
                   quantized=True, quant_scales=(gs, hs), **kw)


_FORCED_OK = ((0, 0, 7, False), (0, 2, 7, False), (1, 2, 7, False))
_FORCED_BAD_ROOT = ((0, 1, 12, False), (0, 2, 7, False), (1, 2, 7, False))
_FORCED_BAD_CHILD = ((0, 0, 7, False), (0, 1, 12, False), (1, 2, 7, False))
_LOOSE = SplitParams(min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0)

# name: (grower, problem, params, keywords).  The expected fingerprints
# are PR 27's (f03d3fc): (leaves, truncated, sha256[:24]).
CASES = {
    "dense": (_serial, _problem(1200), SplitParams(min_data_in_leaf=5),
              dict(max_leaves=15)),
    "pooled4": (_serial, _problem(1200), SplitParams(min_data_in_leaf=5),
                dict(max_leaves=15, hist_slots=4)),
    "pooled8": (_serial, _problem(1200), SplitParams(min_data_in_leaf=5),
                dict(max_leaves=15, hist_slots=8)),
    "gain_gate": (_serial, _problem(1200),
                  SplitParams(min_data_in_leaf=250), dict(max_leaves=7)),
    "gain_gate_pooled4": (_serial, _problem(1200),
                          SplitParams(min_data_in_leaf=250),
                          dict(max_leaves=7, hist_slots=4)),
    "arena_overflow": (_serial, _problem(2000),
                       SplitParams(min_data_in_leaf=5),
                       dict(max_leaves=15, cap_tiles=4)),
    "forced": (_serial, _forced_problem(), _LOOSE,
               dict(max_leaves=7, forced_splits=_FORCED_OK)),
    "forced_invalid_root": (_serial, _forced_problem(), _LOOSE,
                            dict(max_leaves=7,
                                 forced_splits=_FORCED_BAD_ROOT)),
    "forced_invalid_child": (_serial, _forced_problem(), _LOOSE,
                             dict(max_leaves=7,
                                  forced_splits=_FORCED_BAD_CHILD)),
    "forced_past_max_leaves": (_serial, _forced_problem(), _LOOSE,
                               dict(max_leaves=3,
                                    forced_splits=_FORCED_OK)),
    "monotone": (_serial, _problem(1200), SplitParams(min_data_in_leaf=5),
                 dict(max_leaves=15,
                      monotone=jnp.asarray([1, 0, -1, 0, 0], jnp.int32))),
    "categorical": (_serial, _problem(1200),
                    SplitParams(min_data_in_leaf=5),
                    dict(max_leaves=15, is_categorical=jnp.asarray(
                        [False, False, False, True, False]))),
    "quantized": (_quantized, _problem(1200),
                  SplitParams(min_data_in_leaf=5), dict(max_leaves=15)),
    "data_parallel": (_data_parallel, _problem(1024),
                      SplitParams(min_data_in_leaf=5), dict(max_leaves=15)),
}

EXPECTED = {
    "arena_overflow": (4, True, "bf2a596a994700664af69a34"),
    "categorical": (15, False, "369889e3b990daa82d74be3d"),
    "data_parallel": (15, False, "ee72219a14471b9e99c7e5a3"),
    "dense": (15, False, "2e332d514894a073d4c22a70"),
    "forced": (7, False, "ba502fa7b438d994abe38f21"),
    "forced_invalid_child": (7, False, "2ec91e14870fb9cb8684659c"),
    "forced_invalid_root": (7, False, "245a637e6446269f234d9e2c"),
    "forced_past_max_leaves": (3, False, "699c24e6df86628f2f55df3f"),
    "gain_gate": (4, False, "184941afbf43c0e179b11f41"),
    "gain_gate_pooled4": (4, False, "184941afbf43c0e179b11f41"),
    "monotone": (15, False, "7706dafcee0159e01e81ebfa"),
    "pooled4": (15, False, "2e332d514894a073d4c22a70"),
    "pooled8": (15, False, "2e332d514894a073d4c22a70"),
    "quantized": (15, False, "42b5679675f85be1f597d95d"),
}


def _grow(name):
    grower, problem, params, kw = CASES[name]
    return grower(problem, params, **kw)


@pytest.mark.parametrize("name", sorted(CASES))
def test_tree_is_the_parents(name):
    assert _grow(name) == EXPECTED.get(name)


def test_cases_run_the_body_with_no_split():
    """What the cases above are for, read off their fingerprints: the
    gain gate and the overflow end growth short of max_leaves, and the
    pooled cache grows the dense cache's tree."""
    leaves = {k: v[0] for k, v in EXPECTED.items()}
    truncated = {k: v[1] for k, v in EXPECTED.items()}
    assert 1 < leaves["gain_gate"] < 7 and not truncated["gain_gate"]
    assert 1 < leaves["arena_overflow"] < 15 and truncated["arena_overflow"]
    assert leaves["forced_invalid_root"] == leaves["forced_invalid_child"] == 7
    for dense, pooled in (("dense", "pooled4"), ("dense", "pooled8"),
                          ("gain_gate", "gain_gate_pooled4")):
        assert EXPECTED[dense] == EXPECTED[pooled]


# --------------------------------------------------------------------- #
# structure
# --------------------------------------------------------------------- #
_STRUCTURE = {
    "dense": dict(max_leaves=7),
    "pooled": dict(max_leaves=7, hist_slots=4),
    "forced": dict(max_leaves=7, forced_splits=_FORCED_OK),
    "categorical": dict(max_leaves=7, is_categorical=jnp.asarray(
        [False, False, False, True, False])),
}


def _cache_shape(kw):
    return (kw.get("hist_slots") or kw["max_leaves"], F, B, 3)


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _producers(jaxpr, shape, out):
    """Primitive names of the equations, at any depth below `jaxpr`, that
    produce a value of `shape`; a call's own equation is not counted, what
    it calls is."""
    for eqn in jaxpr.eqns:
        subs = list(_sub_jaxprs(eqn))
        for sub in subs:
            _producers(sub, shape, out)
        if not subs and any(getattr(v.aval, "shape", None) == shape
                            for v in eqn.outvars):
            out.append(eqn.primitive.name)
    return out


def _loop_bodies(jaxpr, shape, out):
    """Bodies of the `while` equations that carry a value of `shape`."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while" and any(
                getattr(v.aval, "shape", None) == shape
                for v in eqn.outvars):
            out.append(eqn.params["body_jaxpr"].jaxpr)
        for sub in _sub_jaxprs(eqn):
            _loop_bodies(sub, shape, out)
    return out


_PARAMS = SplitParams(min_data_in_leaf=5)


@functools.lru_cache(maxsize=None)
def _traced(name):
    """The growth program of a structure case, traced once for the jaxpr
    and the HLO tests."""
    return gp.grow_tree_partition.trace(
        *_args(*_problem(300)), _PARAMS, max_bin=B, interpret=True,
        **_STRUCTURE[name])


@pytest.mark.parametrize("name", sorted(_STRUCTURE))
def test_loop_body_only_slice_writes_the_cache(name):
    shape = _cache_shape(_STRUCTURE[name])
    bodies = _loop_bodies(_traced(name).jaxpr.jaxpr, shape, [])
    assert len(bodies) == 1, "the growth loop carries the cache once"
    made = _producers(bodies[0], shape, [])
    assert made == ["dynamic_update_slice"] * 2, made


def test_structure_reader_sees_the_old_form():
    """The reader itself, on a loop written as the body was: two scatters
    and a select over the whole cache."""
    shape = (7, F, B, 3)

    def loop(cache, i0):
        def body(c):
            cache, i = c
            new = cache.at[i].set(cache[i] * 2).at[i + 1].set(cache[i] + 1)
            return jnp.where(i > 3, cache, new), i + 1
        return jax.lax.while_loop(lambda c: c[1] < 5, body, (cache, i0))

    jaxpr = jax.make_jaxpr(loop)(jnp.zeros(shape, jnp.float32),
                                 jnp.int32(0)).jaxpr
    bodies = _loop_bodies(jaxpr, shape, [])
    assert len(bodies) == 1
    assert sorted(set(_producers(bodies[0], shape, []))) == [
        "scatter", "select_n"]
    assert _whole_cache_ops(jax.jit(loop).lower(
        jnp.zeros(shape, jnp.float32), jnp.int32(0)).compile().as_text(),
        "f32[7,%d,%d,3]" % (F, B))


def _computations(hlo):
    """{name: [instruction lines]} of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$",
                     line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.strip() == "}":
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line.strip())
    return comps


def _whole_cache_ops(hlo, cache_type):
    """Instructions inside a `while` body whose result is of the cache's
    type and that are a copy, a select, or a fusion that holds one: each
    makes or reads a second cache beside the carried one."""
    comps = _computations(hlo)
    result = re.compile(r"^(?:ROOT\s+)?%?[\w.\-]+ = " + re.escape(cache_type)
                        + r"(?:\{[^}]*\})? (\w[\w\-]*)\(")

    def moves_cache(line, inside_fusion=False):
        m = result.match(line)
        if not m:
            return False
        if m.group(1) in ("copy", "select"):
            return True
        called = re.search(r"calls=%?([\w.\-]+)", line)
        return (m.group(1) == "fusion" and not inside_fusion and bool(called)
                and any(moves_cache(inner, True)
                        for inner in comps.get(called.group(1), ())))

    bodies = {m.group(1) for lines in comps.values() for line in lines
              for m in [re.search(r"\bwhile\(.*body=%?([\w.\-]+)", line)]
              if m}
    carrying = [b for b in bodies
                if any(result.match(line) for line in comps.get(b, ()))]
    assert carrying, "no while body carries %s: the test reads nothing" \
        % cache_type
    return [line for b in carrying for line in comps[b]
            if moves_cache(line)]


def _assert_no_whole_cache_ops(hlo, name):
    found = _whole_cache_ops(
        hlo, "f32[%d,%d,%d,%d]" % _cache_shape(_STRUCTURE[name]))
    assert not found, "whole-cache operations in the growth loop:\n" \
        + "\n".join(line[:200] for line in found)


@pytest.mark.parametrize("name", ["dense", "pooled"])
def test_compiled_loop_never_copies_or_selects_the_cache(name):
    _assert_no_whole_cache_ops(
        _traced(name).lower().compile().as_text(), name)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip to compile for.  Described here and not at
    import: a process keeps the TPU's library once it has loaded it, and
    under several test workers only the one that runs this file may."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(_STRUCTURE))
def test_compiled_for_v5e_never_copies_or_selects_the_cache(name, one_chip):
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in _args(*_problem(300))]
    # Mosaic lowers the kernels as the program runs them: without x64
    with jax.enable_x64(False):
        hlo = jax.jit(lambda *a: gp.grow_tree_partition_impl(
            *a, _PARAMS, max_bin=B, **_STRUCTURE[name])).lower(
                *shapes).compile().as_text()
    assert "tpu_custom_call" in hlo
    _assert_no_whole_cache_ops(hlo, name)


# C = 48, 64, 160, 2 016 and 416, the widest arena that is one block
@pytest.mark.parametrize("F", [28, 37, 137, 2000, 400])
def test_partition_segment_compiles_for_v5e_at_the_cells_widths(F, one_chip):
    """The tile body's lane gather, its dynamic 16-row slice of the tile in
    VMEM and the transposed sort products pass interpret mode whatever
    Mosaic makes of them: compile the kernel for the chip at the four
    cells' widths, one block and six, and at the widest arena the
    one-block loop's three-deep read ring leaves in one block under the
    default VMEM limit (PR 37: 38 KiB a channel)."""
    C, cap = pp.arena_geometry(100_000, F)
    # one block, under the default limit, serves every cell but Epsilon,
    # whose six blocks of 336 stay
    assert pp.partition_channel_block(C) == {2016: 336}.get(C, C)
    assert C == 2016 or pp._partition_vmem_limit(C, []) is None

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        hlo = jax.jit(lambda arena, mask, feat, xr, cnt: pp.partition_segment(
            arena, jnp.zeros((1, pp.TILE), jnp.float32), 0, cnt, 0,
            60 * pp.TILE, decision=(feat, mask, xr))).lower(
                sds((C, cap), pp.ARENA_DT), sds((256,), jnp.float32),
                *[sds((), jnp.int32)] * 3).compile().as_text()
    assert "tpu_custom_call" in hlo and "partition_segment" in hlo


# ---- a bundled set: the scan reads the bundled histogram (PR 32) --------
def _bundled_args(n=300):
    """Two one-hot blocks of six columns and one plain column, bundled
    into three group columns: (arguments of the growth program, bundle
    maps, number of features)."""
    from lightgbm_tpu.io.efb import BundleInfo
    from lightgbm_tpu.ops import grow as grow_ops
    rng = np.random.RandomState(7)
    nb = np.array([2] * 12 + [B], np.int32)
    info = BundleInfo([list(range(6)), list(range(6, 12)), [12]],
                      nb, np.zeros(13, np.int32))
    bins = np.stack([rng.randint(0, 7, n), rng.randint(0, 7, n),
                     rng.randint(0, B, n)], axis=1).astype(np.uint8)
    maps = grow_ops.bundle_maps(info, nb, np.zeros(13, np.int32), B,
                                feature_scan=False, group_scan=True)
    grad = (np.round(rng.randn(n) * 16) / 16).astype(np.float32)
    hess = (rng.randint(1, 4, n) / 2).astype(np.float32)
    G = 3
    args = (jnp.zeros((pp.arena_channels(G), 8 * pp.TILE), pp.ARENA_DT),
            jnp.asarray(bins.T.astype(np.float32)), jnp.asarray(grad),
            jnp.asarray(hess), jnp.zeros(n, jnp.int32), jnp.ones(13, bool),
            jnp.asarray(nb), jnp.zeros(13, jnp.int32),
            jnp.zeros(13, jnp.int32))
    return args, maps, 13


def _no_per_feature_histogram(text, features):
    """No value of a per-feature histogram's shape [.., F, B, 3]."""
    found = re.findall(r"f32\[(?:\d+,)?%d,%d,3\]" % (features, B), text)
    assert not found, "per-feature histograms in the growth program: %s" \
        % sorted(set(found))


def test_a_bundled_set_is_scanned_without_a_per_feature_histogram(one_chip):
    args, maps, features = _bundled_args()

    def grow(*a, interpret):
        return gp.grow_tree_partition_impl(
            *a[:9], _PARAMS, None, None, None, None, None, a[9],
            max_leaves=8, max_bin=B, full_bag=True, interpret=interpret)

    tree, *_ = jax.jit(functools.partial(grow, interpret=True))(*args, maps)
    assert int(tree.num_leaves) == 8
    assert int(jnp.max(tree.split_feature)) > 2   # feature ids, not groups
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in jax.tree_util.tree_leaves((args, maps))]
    treedef = jax.tree_util.tree_structure((args, maps))

    def flat(*leaves):
        a, m = jax.tree_util.tree_unflatten(treedef, leaves)
        return grow(*a, m, interpret=False)

    with jax.enable_x64(False):
        hlo = jax.jit(flat).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo and "_run_scan" in hlo
    _no_per_feature_histogram(hlo, features)
