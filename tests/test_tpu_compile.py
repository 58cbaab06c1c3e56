"""Ahead-of-time compiles of every TPU-registered kernel for a v5e.

No chip is needed: the TPU compiler compiles for a described topology, and
refuses what the chip would refuse (unaligned blocks, SMEM or VMEM
overflow, primitives Mosaic cannot lower).  Shapes are real widths:
m = 2**20 probes, W = 128 lanes, and the planner's group-space cap for the
one-launch kernel.  Interpret-mode parity lives in test_kernels.py and
test_fused_query.py; this file checks only that the compiled lowering
exists, and which ops of the serving programs the compiler leaves
without an ``op_name``.
"""
import inspect
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.planner import MAX_MEGA_SEGMENTS
from repro.engine.queries import SSB_QUERIES
from repro.kernels.ops import KERNEL_REGISTRY
from repro.serving import PARAM_QUERIES, BatchRunner

M, W, BUCKETS, DELTA_W = 1 << 20, 128, 1 << 16, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back here; keep the cache off around the compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(name):
    """Operand shapes (a pytree of (shape,) tuples) and static kwargs."""
    vec, plane = (M,), (M, W)
    if name == "probe_rows":
        return (vec, plane, plane), {}
    if name == "bucket_probe_stream":
        return ((BUCKETS, W), (BUCKETS, W), vec, vec), {}
    if name == "probe_filter_rows":
        return (vec, plane, plane, plane), {}
    if name == "probe_filter_rows_delta":
        return (vec, plane, plane, plane, vec, (M, DELTA_W),
                (M, DELTA_W)), {}
    if name == "fused_query":
        # two joined dims, one carrying a live delta, at the segment cap
        dims = ((vec, plane, plane),
                (vec, plane, plane, vec, (M, DELTA_W), (M, DELTA_W)))
        return (dims, vec), {"num_segments": MAX_MEGA_SEGMENTS}
    if name == "coalesce_window_mask":
        return (vec,), {}
    raise KeyError(name)


TPU_KERNELS = sorted(n for n, op in KERNEL_REGISTRY.items()
                     if "tpu" in op.backends)


def test_every_registered_kernel_lists_tpu():
    """No registry entry lacks a TPU lowering, so no forced policy can
    reach a kernel that would run interpreted or fall back on the chip."""
    assert TPU_KERNELS == sorted(KERNEL_REGISTRY)


@pytest.mark.parametrize("name", sorted(KERNEL_REGISTRY))
def test_kernel_interpret_resolves_by_backend(name):
    """Every kernel's ``interpret`` defaults to None: compiled on TPU,
    interpreted elsewhere — never interpreted on the chip by default."""
    fn = KERNEL_REGISTRY[name].fn
    fn = getattr(fn, "__wrapped__", fn)
    assert inspect.signature(fn).parameters["interpret"].default is None


@pytest.mark.parametrize("name", TPU_KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    op = KERNEL_REGISTRY[name]
    shapes, kw = _shapes(name)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip),
        shapes, is_leaf=lambda s: isinstance(s, tuple)
        and all(isinstance(d, int) for d in s))
    compiled = jax.jit(
        lambda *a: op.fn(*a, **kw, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- serving programs: which ops the compiled HLO leaves unnamed ----------
SF1_FACT_ROWS = 6_000_000
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?<![\w])(probe|filter|group_key|aggregate)(?![\w])")


def _computations(text):
    """Computation name -> its body lines."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.strip() and line != "}":
            comps[name].append(line)
    return comps


def _named_scopes(comps, comp, seen=None):
    """Scopes of every named op inside ``comp`` and the computations it
    calls."""
    seen = set() if seen is None else seen
    out = set()
    for line in comps.get(comp, []):
        m = _OP_NAME.search(line)
        if m:
            out |= set(_SCOPE.findall(m.group(1))[:1]) or {"other"}
        callee = re.search(r"calls=(%[\w.\-]+)", line)
        if callee and callee.group(1) not in seen:
            seen.add(callee.group(1))
            out |= _named_scopes(comps, callee.group(1), seen)
    return out


@pytest.mark.parametrize("name", sorted(SSB_QUERIES))
def test_serving_programs_unnamed_custom_fusions_are_aggregate(
        name, no_persistent_cache, serving_batch_hlo):
    """The benchmark's device-scope reader counts a serving program's
    custom fusion that carries no ``op_name`` as the group-by's
    scatter-add: at SF 1 shapes every such fusion holds ``aggregate`` ops
    only, and every named op at the entry has one of the four scopes."""
    text = serving_batch_hlo(name)
    comps = _computations(text)
    entry = next(c for c in comps if text.find(f"ENTRY {c} ") >= 0)
    unnamed = 0
    for line in comps[entry]:
        if "kind=kCustom" in line and not _OP_NAME.search(line):
            unnamed += 1
            callee = re.search(r"calls=(%[\w.\-]+)", line).group(1)
            assert _named_scopes(comps, callee) == {"aggregate"}, line[:120]
    if SSB_QUERIES[name].group_by:
        assert unnamed >= 1        # the scatter-add lost its op_name
    assert f"HloModule jit_serve_batch_{name.replace('.', '_')}" in text


_GATHER = re.compile(r"= (\w+\[[\d,]*\])\{?[^ ]* gather\(")


@pytest.mark.parametrize("name", sorted(SSB_QUERIES))
def test_serving_programs_gather_nothing_fact_length(
        name, no_persistent_cache, serving_batch_hlo):
    """The serving batch programs read the cached fact-aligned dimension
    columns: no gather in them, fused or not, yields a fact-length
    result (a dimension predicate or group column gathered through the
    probe's rows per query would)."""
    shapes = _GATHER.findall(serving_batch_hlo(name))
    n = str(SF1_FACT_ROWS)
    assert not [s for s in shapes if n in s], shapes


@pytest.fixture(scope="module")
def serving_operands(one_chip):
    """A serving batch program's operands at SF 1 row counts, as shapes
    on the described chip (column names from a tiny engine): fact columns,
    the fact-aligned dimension columns and the cached probes, each of
    6,000,000 rows."""
    from repro.engine import SSBEngine, generate_ssb

    eng = SSBEngine(generate_ssb(sf=0.002, seed=3), mode="jspim")
    eng.warm_cache()

    def sds(x, n):
        return jax.ShapeDtypeStruct((n,), x.dtype, sharding=one_chip)

    def make(name):
        fact, attrs, probes = BatchRunner._operands(eng, name)
        n = SF1_FACT_ROWS
        return ({k: sds(v, n) for k, v in fact.items()},
                {d: {k: sds(v, n) for k, v in c.items()}
                 for d, c in attrs.items()},
                {d: (sds(f, n), sds(r, n)) for d, (f, r) in probes.items()})

    return make


@pytest.fixture(scope="module")
def serving_batch_hlo(one_chip, serving_operands):
    """The ``serve_batch_<Q>`` programs at width 2, compiled for a v5e
    once per query and shared by the tests that read them."""
    texts = {}

    def get(name):
        if name not in texts:
            fact, attrs, probes = serving_operands(name)
            params = jax.ShapeDtypeStruct(
                (2, PARAM_QUERIES[name].n_params), jnp.int32,
                sharding=one_chip)
            texts[name] = BatchRunner()._batch_program(name).lower(
                fact, attrs, probes, params).compile().as_text()
        return texts[name]

    return get
