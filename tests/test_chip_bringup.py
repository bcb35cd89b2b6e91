"""What the first runs on the v5e found, checked on the CPU (ISSUE 21).

* the kernels lower — and, where this installation's libtpu can describe a
  v5e without owning one, COMPILE — for the TPU at the shapes the chip
  smoke uses: the check that would have caught a decode kernel that had
  only ever run interpreted;
* a Mosaic kernel under a mesh needs ``shard_map``, and ``gpt.loss_fn``
  supplies it;
* the compile cache stays where ``JAX_COMPILATION_CACHE_DIR`` puts it;
* an AOT executable reloads onto the devices it was compiled for;
* ``chip_smoke.py`` refuses to run off the chip, and no document names a
  program that is not in the tree;
* a checkpoint is written in files of bounded size — the driver's chip
  machine refused GPT-2 small's 1.95 GB ``state.npz`` with EFBIG.
"""

import ast
import functools
import io
import os
import re
import shutil
import subprocess
import sys
import tokenize

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_operator_tpu import compile_cache
from paddle_operator_tpu.ops import attention_pallas as ap
from paddle_operator_tpu.parallel import (
    make_mesh, sharded_flash_attention)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ServingEngine defaults at gpt.BASE_CONFIG: 8 rows, 12 heads of 64,
#: 12 layers' 256 + 1 pages of 16 slots, 1024 // 16 pages per sequence
ENGINE = dict(b=8, h=12, d=64, bs=16, layers=12, pages=257, per_seq=64)
#: ``gpt2-small.serve-steady``: 32 rows, 192 + 1 pages of 128 slots
CELL = dict(b=32, h=12, d=64, bs=128, layers=12, pages=193, per_seq=8)
#: chip_smoke's kernel check, the trainer's attention (batch 16), and the
#: other shapes ``_auto_block`` has a measured rule for (bfloat16, S =
#: 1024 / 2048, D = 64 / 128): whatever tile the rule picks there has to
#: lower, and fit the chip's fast memory, without a chip
FLASH_SHAPES = [(2, 12, 1024, 64), (16, 12, 1024, 64), (2, 12, 2048, 64),
                (2, 6, 1024, 128), (2, 6, 2048, 128)]


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _paged_args(dtype, sharding=None, e=ENGINE):
    """q, the two stacked pools, tables, lengths and the layer."""
    pool = (e["layers"], e["pages"], e["bs"], e["h"] * e["d"])
    return (_sds((e["b"], e["h"], e["d"]), dtype, sharding),
            _sds(pool, dtype, sharding), _sds(pool, dtype, sharding),
            _sds((e["b"], e["per_seq"]), jnp.int32, sharding),
            _sds((e["b"],), jnp.int32, sharding),
            _sds((), jnp.int32, sharding))


def _flash_loss(q, k, v):
    return ap.flash_attention(q, k, v, causal=True).astype(
        jnp.float32).sum()


# ---------------------------------------------------------------------------
# (i) the kernels lower for the TPU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_lowers_for_tpu(dtype):
    exp = jax.export.export(jax.jit(ap.paged_decode_attention),
                            platforms=("tpu",))(*_paged_args(dtype))
    assert "tpu_custom_call" in exp.mlir_module()


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_fwd_bwd_lowers_for_tpu(shape):
    q = _sds(shape, jnp.bfloat16)
    exp = jax.export.export(
        jax.jit(jax.value_and_grad(_flash_loss, argnums=(0, 1, 2))),
        platforms=("tpu",))(q, q, q)
    # forward, dQ and dK/dV kernels
    assert exp.mlir_module().count("tpu_custom_call") >= 3


@pytest.fixture(scope="module")
def v5e():
    """One device of a described (not owned) v5e 2x2: lets the real TPU
    compiler — Mosaic included — run in a sandbox with no chip."""
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # no libtpu here, or it cannot describe one
        pytest.skip("no TPU topology description available: %r" % (e,))
    return topo.devices


@pytest.mark.parametrize("shapes", [ENGINE, CELL], ids=["engine", "cell"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_compiles_for_v5e(v5e, dtype, shapes):
    """The kernel reads the stacked pools where they lie: no temporary
    of a pool's size (a pool of the cell is 0.91 GB), which a slice, a
    reshape or a transpose of one outside the kernel would be."""
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = jax.jit(ap.paged_decode_attention).lower(
        *_paged_args(dtype, sh, shapes)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("shape", FLASH_SHAPES[:1] + FLASH_SHAPES[2:])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, shape):
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    q = _sds(shape, jnp.bfloat16, sh)
    compiled = jax.jit(jax.value_and_grad(
        _flash_loss, argnums=(0, 1, 2))).lower(q, q, q).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= 3


def test_mla_paged_decode_compiles_for_v5e_without_copying_its_pool(v5e):
    """At the shapes of ``axk1-share16.serve-decode-1k``: 64 rows x 64
    heads over a seven-layer pool of bfloat16 pages whose rows are padded
    to whole lanes. With rows of 576 XLA lays the pool out token-minor
    and copies all of it before every call (2.6 GB of temporaries)."""
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    compiled = jax.jit(lambda ql, qr, pool, tables, lens, layer:
                       ap.mla_paged_decode(ql, qr, pool, tables, lens, 0.1,
                                           layer=layer)).lower(
        _sds((64, 64, 512), jnp.bfloat16, sh),
        _sds((64, 64, 64), jnp.bfloat16, sh),
        _sds((7, 2305, 128, 640), jnp.bfloat16, sh),
        _sds((64, 36), jnp.int32, sh), _sds((64,), jnp.int32, sh),
        _sds((), jnp.int32, sh)).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_sparse_decode_compiles_for_v5e_without_copying_its_pools(v5e):
    """At the shapes of ``dsv32-share32.serve-long-8k``: 16 rows of up to
    260 pages, an indexer of 64 heads x 128 over a six-layer pool of
    bfloat16 keys, the top 2048 of 33,280 scores, their latent rows
    fetched through the block table for 128 heads. Two Mosaic calls; the
    gather reads the flat pool where it lies (the two pools are 4.9 GB:
    a copy of either would show)."""
    sh = jax.sharding.SingleDeviceSharding(v5e[0])

    def sparse(q_idx, w, keys, ql, qr, latent, tables, lens, layer):
        scores = ap.dsa_index_scores(q_idx, w, keys, tables, lens,
                                     layer=layer)
        chosen, count = ap.select_rows(scores, lens, 2048)
        return ap.mla_selected_decode(ql, qr, latent, tables, chosen, count,
                                      0.1, layer=layer)

    compiled = jax.jit(sparse).lower(
        _sds((16, 64, 128), jnp.bfloat16, sh), _sds((16, 64), jnp.float32, sh),
        _sds((6, 4161, 128, 128), jnp.bfloat16, sh),
        _sds((16, 128, 512), jnp.bfloat16, sh),
        _sds((16, 128, 64), jnp.bfloat16, sh),
        _sds((6, 4161, 128, 640), jnp.bfloat16, sh),
        _sds((16, 260), jnp.int32, sh), _sds((16,), jnp.int32, sh),
        _sds((), jnp.int32, sh)).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


def test_paged_cache_prefill_write_compiles_for_v5e_in_place(v5e):
    """At the shapes of ``gpt2-small.serve-steady``: the K and the V pool
    of 12 layers x (192 + 1) pages of 128 slots (0.91 GB each) and a
    prefill's rows padded to 512. The one write program updates both
    donated pools where they lie: no temporary of a pool's size, every
    pool's bytes aliased to a result."""
    from paddle_operator_tpu.serving.kv_cache import PagedKvCache

    cache = PagedKvCache(2, 8, layers=1, heads=2, head_dim=8)
    cache.allocator.alloc_sequence("s", 3)
    tiny = jnp.zeros((1, 8, 16))
    cache.write_rows("s", (tiny, tiny), 3)   # builds the jitted write
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    pool = _sds((12, 193, 128, 768), jnp.float32, sh)
    rows = _sds((12, 512, 768), jnp.float32, sh)
    compiled = cache._write.lower(
        (pool, pool), (rows, rows), _sds((4,), jnp.int32, sh)).compile()
    pool_bytes = 12 * 193 * 128 * 768 * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes // 8
    assert mem.alias_size_in_bytes >= 2 * pool_bytes


def test_gpts_decode_step_compiles_for_v5e_without_copying_a_pool(
        v5e, monkeypatch):
    """``gpt2-small.serve-steady``'s decode step as the engine jits it
    (pools donated, the kernel compiled, not interpreted), for a
    described v5e: both pools are aliased to the step's results, its
    temporaries are no pool's size (before the stacked layout: a copy
    and a transpose of each of 24 pools, 2.86 GB), the 24 row writes are
    scatters in place and no other operation yields a pool."""
    from paddle_operator_tpu.models import gpt

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    cfg, c = dict(gpt.BASE_CONFIG), CELL
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: gpt.init(jax.random.PRNGKey(0), cfg)))
    _, pool, _, tables, row, _ = _paged_args(jnp.float32, sh, c)
    decode = gpt.serve_decode(cfg, "paged", c["bs"], c["pages"] - 1)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, (pool, pool), row, row, tables, row,
        _sds(row.shape, jnp.bool_, sh)).compile()
    pool_bytes = int(np.prod(pool.shape)) * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 12
    yields_a_pool = re.findall(
        r"= f32\[12,193,128,768\]\S* ([\w-]+)\(", text)
    assert set(yields_a_pool) <= {"parameter", "scatter", "fusion",
                                  "get-tuple-element", "bitcast"}
    assert yields_a_pool.count("scatter") == 24


def test_the_looped_decode_step_compiles_for_v5e_copying_no_pool_and_no_weight(
        v5e, monkeypatch):
    """``ouro-2.6b.serve-reason-1k``'s decode step at the published sizes
    (48 layers run four times over, 8 rows, both pools ``bf16[192, 41,
    128, 2048]`` donated: 8.25 GB beside 5.34 GB of weights) for a
    described v5e: ONE scan over the loop steps whose body holds the 48
    layers — 48 Mosaic calls, each loop step's 96 row writes scatters in
    place, both pools aliased to the results, temporaries a hundredth of
    a pool, and no operation that copies, transposes or slices an array
    of a weight's shape (a scan over stacked weights slices all seven of
    a layer's kernels into fresh buffers: PERF.md, section 6, PR 41)."""
    from paddle_operator_tpu.models import ouro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    cfg = dict(ouro.BASE_CONFIG, max_seq=1280)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: ouro.init(jax.random.PRNGKey(0), cfg)))
    pool = _sds((192, 41, 128, 2048), jnp.bfloat16, sh)
    row = _sds((8,), jnp.int32, sh)
    decode = ouro.serve_decode(cfg, "paged", 128, 40)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, (pool, pool), row, row, _sds((8, 10), jnp.int32, sh), row,
        _sds((8,), jnp.bool_, sh)).compile()
    pool_bytes = 192 * 41 * 128 * 2048 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 48
    yields_a_pool = re.findall(
        r"= bf16\[192,41,128,2048\]\S* ([\w-]+)\(", text)
    assert set(yields_a_pool) <= {"parameter", "scatter", "fusion",
                                  "get-tuple-element", "bitcast", "while",
                                  "tuple"}
    assert yields_a_pool.count("scatter") == 96
    assert not re.findall(
        r"= bf16\[(?:1,)?(?:2048,5632|5632,2048|2048,2048)\]\S* "
        r"(?:copy|transpose|dynamic-slice)\(", text)


def test_the_two_state_decode_step_compiles_for_v5e_with_every_pool_in_place(
        v5e, monkeypatch):
    """``minicpm-sala-pp2.serve-doc-16k``'s decode step at the published
    sizes (4 block-sparse and 12 lightning layers, 16 rows; K and V
    ``bf16[4, 4353, 128, 256]``, compressed keys ``bf16[4, 4353, 8,
    256]``, states ``f32[12, 17, 32, 128, 128]``, all donated: 2.78 GB
    beside 10.08 GB of weights) for a described v5e: the grouped-query
    block kernel compiles (4 Mosaic calls, its blocks read in place
    through the prefetched page list), all four pools are aliased to the
    step's results, the temporaries are a hundredth of them, K, V and
    the compressed keys are written by scatters in place and a lightning
    layer's whole pass over its states is ONE update in place."""
    from paddle_operator_tpu.models import minicpm_sala as sala

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    cfg = dict(sala.BASE_CONFIG, layers=16, layer_offset=8,
               mixer_types=sala.BASE_CONFIG["mixer_types"][8:24],
               max_seq=34816)
    params = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sh),
        jax.eval_shape(lambda: sala.init(jax.random.PRNGKey(0), cfg)))
    shapes = jax.eval_shape(lambda: sala.serve_cache(cfg, 4352, 128, 16).pools())
    pools = tuple(_sds(a.shape, a.dtype, sh) for a in shapes)
    assert [a.shape for a in pools] == [
        (4, 4353, 128, 256), (4, 4353, 128, 256), (4, 4353, 8, 256),
        (12, 17, 32, 128, 128)]
    row = _sds((16,), jnp.int32, sh)
    decode = sala.serve_decode(cfg, "paged", 128, 4352)
    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pools, row, row, _sds((16, 273), jnp.int32, sh), row,
        _sds((16,), jnp.bool_, sh)).compile()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pools)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 64
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4

    def yields(shape):
        return re.findall(r"= " + re.escape(shape) + r"\S* ([\w-]+)\(",
                          text)

    in_place = {"parameter", "scatter", "fusion", "get-tuple-element",
                "bitcast", "dynamic-update-slice"}
    for shape in ("bf16[4,4353,128,256]", "bf16[4,4353,8,256]",
                  "f32[12,17,32,128,128]"):
        assert set(yields(shape)) <= in_place, shape
    assert yields("f32[12,17,32,128,128]").count(
        "dynamic-update-slice") == 12


def _written_out(text, shapes):
    """The instructions of a compiled module's text that write an array
    of one of ``shapes`` out: a fusion, a slice or a copy that stands in
    a computation that is not itself a fusion's."""
    fused, found, comp = set(), [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        if " fusion(" in line:
            fused.update(re.findall(r"calls=%([\w.\-]+)", line))
        yields = re.search(r"%([\w.\-]+) = (\w+\[[\d,]*\])\S* "
                           r"(fusion|dynamic-slice|copy)\(", line)
        if yields and yields.group(2) in shapes:
            found.append((comp, yields.group(1)))
    return [name for comp, name in found if comp not in fused]


@pytest.mark.parametrize("rows", [16, 1024], ids=["decode", "prefill-chunk"])
def test_a_missed_experts_kernels_are_not_copied_for_v5e(v5e, rows):
    """``dsv32-share32``'s expert layer under its scan over five layers
    (8 held of 256 experts of 7168 x 2048, top-8 in 4 of 8 groups; a
    decode step's 16 rows, a prefill chunk's 1024) for a described v5e.
    An expert's three kernels are 29.4 MB each. Sliced inside a loop
    over an expert's row blocks inside a loop over the experts, they do
    not depend on the inner index: the compiler lifts the slices into
    the loop over the EXPERTS and copies every held expert's 88 MB into
    fast memory every layer and step, hit or not
    (``constant_dynamic-slice_fusion bf16[1,1,...]``, 4.9 ms of
    ``dsv32-share32.serve-long-8k``'s 16.1 ms step at 2 pairs a step:
    PERF.md, section 6, PR 51). In ``moe_share_apply``'s ONE loop over
    the blocks that exist nothing writes an array of an expert's kernel
    out: each product's fusion slices the stacked ``bf16[5,8,...]`` in
    place, and an expert without a pair has no block."""
    from paddle_operator_tpu.ops import moe

    sh = jax.sharding.SingleDeviceSharding(v5e[0])
    layers, g, d, f, routed_experts = 5, 8, 7168, 2048, 256
    bf = jnp.bfloat16
    routed = {"gate": _sds((layers, g, d, f), bf, sh),
              "up": _sds((layers, g, d, f), bf, sh),
              "down": _sds((layers, g, f, d), bf, sh)}
    sliced = {"router": _sds((layers, d, routed_experts), bf, sh),
              "bias": _sds((layers, routed_experts), jnp.float32, sh),
              "shared": {"gate": _sds((layers, d, f), bf, sh),
                         "up": _sds((layers, d, f), bf, sh),
                         "down": _sds((layers, f, d), bf, sh)}}

    def stack(routed, sliced, z, live):
        def body(z, xs):
            index, p = xs
            out, counters = moe.moe_share_apply(
                dict(routed, router=p["router"], shared=p["shared"]), z,
                tuple(range(g)), 8, 2.5, live=live, layer=index, n_group=8,
                topk_group=4, bias=p["bias"])
            return z + out, counters

        return jax.lax.scan(body, z,
                            (jnp.arange(layers, dtype=jnp.int32), sliced))

    text = jax.jit(stack).lower(
        routed, sliced, _sds((rows, d), jnp.float32, sh),
        _sds((rows,), jnp.bool_, sh)).compile().as_text()
    assert not _written_out(
        text, {"bf16[1,1,%d,%d]" % (d, f), "bf16[1,1,%d,%d]" % (f, d)})


@pytest.mark.parametrize("layer", [0, 5, 11])
def test_paged_decode_matches_reference_interpreted(layer):
    """The kernel (products on the VPU, a head's sum over its lanes as a
    0/1 matmul) against the gather-einsum reference at the engine's head
    shape, the first, a middle and the last layer of the stack."""
    e = dict(ENGINE, pages=33, per_seq=8)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = (e["layers"], e["pages"], e["bs"], e["h"] * e["d"])
    q = jax.random.normal(ks[0], (e["b"], e["h"], e["d"]))
    kp, vp = jax.random.normal(ks[1], pool), jax.random.normal(ks[2], pool)
    tables = jax.random.randint(ks[3], (e["b"], e["per_seq"]), 0,
                                e["pages"] - 1)
    lens = jax.random.randint(ks[4], (e["b"],), 1,
                              e["per_seq"] * e["bs"] + 1)
    got = ap.paged_decode_attention(q, kp, vp, tables, lens, layer,
                                    interpret=True)
    want = ap._reference_paged_decode(q, kp, vp, tables, lens,
                                      1.0 / np.sqrt(e["d"]), layer)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ---------------------------------------------------------------------------
# a Mosaic kernel under a mesh
# ---------------------------------------------------------------------------

def _export_over_mesh(fn, mesh, shape):
    sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("dp"))
    q = _sds(shape, jnp.bfloat16)
    return jax.export.export(
        jax.jit(fn, in_shardings=(sh, sh, sh), out_shardings=sh),
        platforms=("tpu",))(q, q, q)


def test_bare_kernel_under_a_mesh_does_not_lower():
    """Why sharded_flash_attention exists: GSPMD cannot partition the
    kernel, so the default dp=n trainer on a four-chip host failed at
    lowering."""
    mesh = make_mesh({"dp": 4}, jax.devices()[:4])
    with pytest.raises(NotImplementedError, match="shard_map"):
        _export_over_mesh(
            lambda q, k, v: ap.flash_attention(q, k, v, causal=True),
            mesh, (16, 12, 1024, 64))


def test_sharded_flash_lowers_under_a_mesh(monkeypatch):
    # compiled, not interpreted, as on the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh({"dp": 4}, jax.devices()[:4])
    exp = _export_over_mesh(
        functools.partial(sharded_flash_attention, mesh=mesh, causal=True),
        mesh, (16, 12, 1024, 64))
    assert "tpu_custom_call" in exp.mlir_module()


def test_sharded_flash_matches_reference_on_a_mesh():
    """Interpreted on the CPU mesh: batch over dp, heads over tp."""
    from paddle_operator_tpu.parallel.context import reference_attention

    mesh = make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, 256, 64)) for kk in ks)
    got = sharded_flash_attention(q, k, v, mesh, causal=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_sharded_flash_unsupported_shape_takes_dense_path():
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 32, 16))
    from paddle_operator_tpu.parallel.context import reference_attention

    np.testing.assert_allclose(
        sharded_flash_attention(q, q, q, mesh, causal=True),
        reference_attention(q, q, q, causal=True), atol=1e-6)


def test_gpt_loss_routes_auto_attention_through_shard_map(monkeypatch):
    """On the TPU backend with a mesh, attn_impl="auto" must reach the
    kernel through shard_map: the exported step holds the Mosaic call."""
    from paddle_operator_tpu.models import gpt

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh({"dp": 4}, jax.devices()[:4])
    cfg = dict(gpt.TINY_CONFIG, layers=1, heads=2, max_seq=256)
    params = jax.eval_shape(lambda k: gpt.init(k, cfg),
                            jax.random.PRNGKey(0))
    batch = {"input_ids": _sds((4, 256), jnp.int32)}
    loss = functools.partial(gpt.loss_fn, mesh=mesh)
    exp = jax.export.export(
        jax.jit(lambda p, b: jax.grad(lambda p: loss(p, b)[0])(p),
                in_shardings=(None, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec("dp")))),
        platforms=("tpu",))(params, batch)
    assert "tpu_custom_call" in exp.mlir_module()


# ---------------------------------------------------------------------------
# (ii) where the compile cache lives
# ---------------------------------------------------------------------------

_PLACEMENT_PROBE = """
import json, os, sys
sys.path.insert(0, %(repo)r)
import jax, jax.numpy as jnp
updates = []
real = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]
from paddle_operator_tpu import compile_cache
step = compile_cache.cached_jit(lambda x: x * 2 + 1, (jnp.ones((8,)),),
                                label="probe")
step(jnp.ones((8,)))
print(json.dumps({"updates": updates,
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "block": compile_cache.startup_block()["dir"]}))
"""


def test_jax_compilation_cache_dir_is_honoured(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set nothing re-points JAX's cache,
    and the AOT executables land under the same root — even with the
    pods' own variable pointing elsewhere."""
    import json

    jax_dir, other = tmp_path / "jaxcache", tmp_path / "other"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(jax_dir),
               TPUJOB_COMPILE_CACHE_DIR=str(other))
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_PROBE % {"repo": REPO}],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert "jax_compilation_cache_dir" not in got["updates"]
    assert got["jax_dir"] == got["block"] == str(jax_dir)
    assert [f for f in os.listdir(jax_dir / "aot") if f.endswith(".aotx")]
    assert [f for f in os.listdir(jax_dir) if f != "aot"], \
        "no XLA cache entry beside the AOT dir"
    assert not other.exists()


def test_default_cache_dir_is_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TPUJOB_COMPILE_CACHE_DIR", raising=False)
    assert compile_cache.default_cache_dir() == os.path.join(
        REPO, ".compile_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".compile_cache/" in fh.read().split()


def test_cache_dir_precedence(monkeypatch):
    monkeypatch.setenv("TPUJOB_COMPILE_CACHE_DIR", "/vol/tpujob")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.default_cache_dir() == "/vol/tpujob"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/vol/jax")
    assert compile_cache.default_cache_dir() == "/vol/jax"


# ---------------------------------------------------------------------------
# (iii) AOT executables reload onto their own devices
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUJOB_COMPILE_CACHE_DIR", str(tmp_path))
    compile_cache.reset_stats_for_tests()
    yield tmp_path
    compile_cache.reset_stats_for_tests()


def test_one_device_executable_reloads_among_eight(fresh_cache):
    """deserialize_and_load defaults to EVERY device of the backend; an
    executable built for one then refuses its first call."""
    assert len(jax.devices()) == 8
    x = jnp.arange(8.0)
    cold = compile_cache.cached_jit(lambda a: a * 3, (x,), label="one")
    assert cold.source == "compiled"
    compile_cache.reset_stats_for_tests()          # drop the memo
    warm = compile_cache.cached_jit(lambda a: a * 3, (x,), label="one")
    assert warm.source == "aot"
    np.testing.assert_array_equal(warm(x), x * 3)
    s = compile_cache.stats()
    assert s["first_call_rejects"] == 0 and s["jit_fallbacks"] == 0


def test_submesh_executable_reloads_in_device_order(fresh_cache):
    mesh = make_mesh({"dp": 4}, jax.devices()[2:6])
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp"))
    x = jax.device_put(jnp.arange(8.0), sh)
    build = functools.partial(
        compile_cache.cached_jit, lambda a: a + 1, (x,), mesh=mesh,
        in_shardings=(sh,), out_shardings=sh, label="sub")
    assert build().source == "compiled"
    compile_cache.reset_stats_for_tests()
    warm = build()
    assert warm.source == "aot"
    out = warm(x)
    np.testing.assert_array_equal(out, np.arange(8.0) + 1)
    assert out.sharding.device_set == set(jax.devices()[2:6])
    assert compile_cache.stats()["first_call_rejects"] == 0


def test_first_call_reject_is_counted(fresh_cache):
    def refuse(*a):
        raise ValueError("expected 8 shards, got 1")

    step = compile_cache.CachedStep(
        refuse, "aot", "f" * 32, 0.0, fallback=lambda: (lambda a: a + 1))
    assert step(1) == 2 and step.source == "jit"
    assert compile_cache.stats()["first_call_rejects"] == 1


def test_aot_lowering_failure_is_counted(fresh_cache):
    def cannot_trace(a):
        raise TypeError("not traceable")

    step = compile_cache.cached_jit(cannot_trace, (jnp.ones(2),),
                                    label="bad")
    assert step.source == "jit"
    assert compile_cache.stats()["aot_lower_failures"] == 1
    assert compile_cache.startup_block()["aot_lower_failures"] == 1


# ---------------------------------------------------------------------------
# (iv) off the chip: no result
# ---------------------------------------------------------------------------

def _run(script, *args, cwd=REPO, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_fails_without_a_chip():
    out = _run("chip_smoke.py")
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout and "CHIP_SMOKE OK" not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run("chip_smoke.py", "--rehearse-on-cpu", cwd=str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "CHIP_SMOKE OK" not in out.stdout


def test_chip_smoke_rehearsal_runs_the_tiny_config_to_the_end():
    out = _run("chip_smoke.py", "--rehearse-on-cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("CHIP_SMOKE")]
    assert lines[-1].startswith("CHIP_SMOKE OK")
    assert all(l.endswith("platform=cpu DRY RUN") for l in lines[1:])
    # a rehearsal is not a result
    assert '"ok"' not in out.stdout
    assert any("resume_steps=[3]" in l for l in lines)


def _documents():
    """(path, text) of what tells a reader which file to run or read; of a
    Python source, its comments and docstrings."""
    paths = [os.path.join(REPO, n)
             for n in ("Makefile", "README.md", "pyproject.toml")]
    paths += sorted(os.path.join(REPO, "docs", n)
                    for n in os.listdir(os.path.join(REPO, "docs"))
                    if n.endswith(".md"))
    for top in ("scripts", "paddle_operator_tpu"):
        for where, dirs, names in os.walk(os.path.join(REPO, top)):
            dirs[:] = sorted(d for d in dirs if d != "__pycache__")
            paths += [os.path.join(where, n) for n in sorted(names)
                      if n.endswith(".py")]
    for path in paths:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        if path.endswith(".py"):
            said = [ast.get_docstring(node, clean=False) or ""
                    for node in ast.walk(ast.parse(text))
                    if isinstance(node, (ast.Module, ast.ClassDef,
                                         ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            said += [tok.string for tok in tokenize.generate_tokens(
                io.StringIO(text).readline) if tok.type == tokenize.COMMENT]
            text = "\n".join(said)
        yield os.path.relpath(path, REPO), text


def test_every_file_a_document_names_is_in_the_tree():
    """A sentence that sends a reader to a file that is gone is a defect:
    each ``scripts/<name>.py``, each ``BENCH_*.json`` and each ``*.py``
    named without a directory (a program at the root of the checkout, or a
    module by its file name) exists in the tree."""
    modules = set()
    for _, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        modules.update(n for n in names if n.endswith(".py"))
    rules = [
        (re.compile(r"\bscripts/(\w+\.py)\b"), lambda n: os.path.exists(
            os.path.join(REPO, "scripts", n)), "scripts/"),
        (re.compile(r"\b(BENCH_[A-Z_]+\.json)\b"), lambda n: os.path.exists(
            os.path.join(REPO, n)), ""),
        (re.compile(r"(?<![\w/.*\-])([A-Za-z_]\w*\.py)\b"),
         modules.__contains__, ""),
    ]
    gone = ["%s names %s%s" % (here, prefix, name)
            for here, text in _documents()
            for pattern, in_tree, prefix in rules
            for name in sorted(set(pattern.findall(text)))
            if not in_tree(name)]
    assert gone == [], "\n".join(gone)


# ---------------------------------------------------------------------------
# a checkpoint no file of which outgrows a bound
# ---------------------------------------------------------------------------

def _ckpt_state():
    rng = np.random.default_rng(0)
    return {"params": {"w": rng.random((512, 1024), np.float32),
                       "b": np.arange(7)},
            "opt": [rng.random(70000), np.float32(3.0)],
            "step": np.int32(4)}


def _same(got, want):
    return all(np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want),
        strict=True))


def test_checkpoint_is_cut_into_bounded_files(tmp_path):
    from paddle_operator_tpu.utils import checkpoint as ckpt

    state, d = _ckpt_state(), str(tmp_path)
    small = ckpt.save_checkpoint(d, 1, {"w": np.arange(4)})
    assert sorted(os.listdir(small)) == ["manifest.json", "state.npz"]
    big = ckpt.save_checkpoint(d, 2, state, part_bytes=300_000)
    names = sorted(os.listdir(big))
    assert names[:3] == ["manifest.json", "state.npz.000", "state.npz.001"]
    assert max(os.path.getsize(os.path.join(big, n)) for n in names) \
        <= 300_000
    got, manifest = ckpt.restore_checkpoint(d)
    assert manifest["step"] == 2
    assert manifest["state_parts"] == len(names) - 1 > 8
    assert _same(got, state)
    # a lost part is a corrupt step: quarantined, and resume walks back
    os.remove(os.path.join(big, "state.npz.004"))
    with pytest.raises(ckpt.CorruptCheckpointError, match="state.npz.004"):
        ckpt.restore_checkpoint(d, 2)
    assert ckpt.restore_latest(d)[1]["step"] == 1
    assert os.path.isdir(big + ".corrupt")


def test_checkpoint_is_written_under_a_file_size_limit(tmp_path):
    """RLIMIT_FSIZE below the state's size (what the chip machine had):
    the writer cuts its files to the limit instead of dying of EFBIG."""
    code = (
        "import resource, sys, numpy as np\n"
        "from paddle_operator_tpu.utils import checkpoint as ckpt\n"
        "resource.setrlimit(resource.RLIMIT_FSIZE, (1 << 20, 1 << 20))\n"
        "state = {'w': np.arange(1 << 20, dtype=np.float32)}\n"
        "ckpt.save_checkpoint(sys.argv[1], 3, state)\n"
        "got, _ = ckpt.restore_latest(sys.argv[1])\n"
        "assert np.array_equal(got['w'], state['w'])\n")
    out = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                         cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    step = os.path.join(str(tmp_path), "step_%012d" % 3)
    sizes = [os.path.getsize(os.path.join(step, n))
             for n in os.listdir(step)]
    assert len(sizes) == 6 and max(sizes) == 1 << 20


# ---------------------------------------------------------------------------
# what the runner banks for the hardware block
# ---------------------------------------------------------------------------

def test_mfu_divides_by_every_device_the_step_spans():
    from paddle_operator_tpu.obs.hardware import (
        ChipSpec, HardwarePlane, analytic_cost, conservation_violations)

    plane = HardwarePlane(ChipSpec("x", "tpu", 100e12, 800e9, "registry"))
    plane.set_cost(analytic_cost(40e12), devices=4)
    plane.record(10, 10.0)
    blk = plane.block()
    assert blk["devices"] == 4 and blk["mfu"] == pytest.approx(0.1)
    assert plane.mfu_of_rate(1.0) == pytest.approx(0.1)
    assert conservation_violations(blk) == []


def test_runner_banks_synced_windows_without_the_warm_up_call(monkeypatch):
    """Every banked window ends in block_until_ready, and the step
    function's first call (compile + first execution) is in none."""
    import paddle_operator_tpu.runner as runner_mod
    from paddle_operator_tpu.models import gpt
    from paddle_operator_tpu.obs.hardware import HardwarePlane
    from paddle_operator_tpu.ops import optim

    events = []
    real_sync = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (events.append("sync"), real_sync(x))[1])
    real_record = HardwarePlane.record
    monkeypatch.setattr(
        HardwarePlane, "record",
        lambda self, steps, secs: (events.append(("bank", steps)),
                                   real_record(self, steps, secs))[1])
    job = runner_mod.TrainJob(
        init_params=lambda rng: gpt.init(rng, gpt.TINY_CONFIG),
        loss_fn=gpt.loss_fn, optimizer=optim.adamw(1e-3),
        make_batch=lambda rng, step: gpt.synthetic_batch(rng, 8, 16, 1024),
        total_steps=5, log_every=2)
    res = runner_mod.run_training(job, init_distributed=False)
    banks = [e for e in events if e != "sync"]
    # step 1 is warm-up; windows close at the log boundaries (2, 4) and
    # at the end of the run (5)
    assert banks == [("bank", 1), ("bank", 2), ("bank", 1)]
    for i, e in enumerate(events):
        if e != "sync":
            assert events[i - 1] == "sync"
    assert res["hardware"]["steps"] == 4 and res["steps"] == 5
