"""Compile-only checks of the main-path Pallas kernels at real widths,
with the TPU compiler, for a described (not attached) v5e chip.

Nothing runs: the compiler refuses what the chip would refuse (block
tiling, dot forms, scoped VMEM), which interpret-mode tests cannot see.
The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library.
"""
import contextlib
import dataclasses
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import configs
from repro.dist import sharding as shd
from repro.kernels import dcim_mvm, paged_attention, pareto_rank, selective_scan
from repro.models import common, lm
from repro.serve import scheduler

# The serving shape of chip_smoke.py's serve phase.
SLOTS, MAX_LEN, PAGE = 4, 1024, 16
# The serving cells' pool: 64 slots of 2048 tokens in pages of 16, and
# the garbage page.
CELL_SLOTS, CELL_NB = 64, 2048 // PAGE
CELL_PAGES = CELL_SLOTS * CELL_NB + 1


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_paged_decode_gqa_qwen(one_chip):
    cfg = configs.get_config("qwen2.5-3b")
    nb = MAX_LEN // PAGE
    n_pages = SLOTS * nb + 1
    pages = (cfg.n_layers, n_pages, PAGE, cfg.n_kv, cfg.head_dim)
    _compile(
        paged_attention.paged_decode_gqa_pallas,
        _sds(one_chip, (SLOTS, 1, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        _sds(one_chip, pages, jnp.bfloat16),
        _sds(one_chip, pages, jnp.bfloat16),
        _sds(one_chip, (SLOTS, nb), jnp.int32),
        _sds(one_chip, (SLOTS,), jnp.int32),
        _sds(one_chip, (), jnp.int32),
    )


@pytest.mark.parametrize("W,T", [(4, 512), (1, 64)])
def test_prefix_prefill_qwen(one_chip, W, T):
    cfg = configs.get_config("qwen2.5-3b")
    kv = lambda n: _sds(one_chip, (W, n, cfg.n_kv, cfg.head_dim), jnp.bfloat16)
    _compile(
        paged_attention.prefix_prefill_pallas,
        _sds(one_chip, (W, T, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
        kv(MAX_LEN), kv(MAX_LEN), kv(T), kv(T),
        _sds(one_chip, (W,), jnp.int32),
    )


def test_dominance_vmapped_over_scenarios(one_chip):
    _compile(jax.vmap(pareto_rank.dominance_matrix_pallas),
             _sds(one_chip, (8, 256, 4), jnp.float32),
             _sds(one_chip, (8, 256), jnp.float32))


def test_dcim_mvm_dense_shape(one_chip):
    _compile(dcim_mvm.dcim_mvm_pallas,
             _sds(one_chip, (256, 2048), jnp.int8),
             _sds(one_chip, (2048, 11008), jnp.int8))


def test_selective_scan_jamba(one_chip):
    cfg = configs.get_config("jamba-v0.1-52b")
    D = cfg.ssm.expand * cfg.d_model
    N = cfg.ssm.d_state
    B, S = 1, 512
    f32 = lambda *s: _sds(one_chip, s, jnp.float32)
    assert (D, N) == (8192, 16)
    _compile(selective_scan.selective_scan_pallas,
             f32(B, S, D), f32(B, S, D), f32(B, S, N), f32(B, S, N),
             f32(D, N), f32(D))


@pytest.mark.parametrize("tokens", [64, 1536])
def test_row_parallel_partial_sums_all_reduce_in_bf16(v5e_2x2, tokens):
    """``wo`` of mistral-nemo-12b at tp=4, for a decode step's 64 rows and
    a 1536-token prefill: an all-reduce of float32 partial sums followed
    by the cast to bf16 compiles to one bf16 all-reduce, the same one
    ``common.dense_row_parallel`` asks for by casting first."""
    mesh = shd.make_mesh((4,), ("model",), devices=v5e_2x2.devices[:4])
    x = jax.ShapeDtypeStruct((tokens, 4096), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "model")))
    w = jax.ShapeDtypeStruct((4096, 5120), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("model", None)))

    def float32_sums(x, w):
        y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
        y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P()))
        return y.astype(jnp.bfloat16)

    def program(x, w):
        with shd.use_mesh(shd.serving_context(mesh)):
            return common.dense_row_parallel({"w": w}, x, "heads", "repro.tp.attn_out")

    for fn in (float32_sums, program):
        hlo = jax.jit(fn).lower(x, w).compile().as_text()
        dtypes = re.findall(r"= (\w+)\[[^\]]*\]\S* all-reduce(?:-start)?\(", hlo)
        assert dtypes == ["bf16"], (fn.__name__, dtypes)


# One HLO instruction: its result type and its opcode.
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", re.M)


@pytest.mark.parametrize("arch,tp,program", [
    ("qwen2.5-3b", 1, "decode"),
    ("qwen2.5-3b", 1, "prefill"),
    ("qwen2.5-3b", 1, "prefill_with_context"),
    ("mistral-nemo-12b", 4, "decode"),
])
def test_layer_scan_updates_pool_in_place(v5e_2x2, arch, tp, program):
    """The serving programs at the cells' pool, 4 layers deep: the layer
    scan carries the pool and updates each layer's pages in place, so
    no copy, dynamic-slice or dynamic-update-slice yields a layer's slab
    of the pool (per chip) or the whole stacked pool, and the program's
    temporaries stay under one layer's K slab."""
    cfg = dataclasses.replace(configs.get_config(arch), n_layers=4,
                              attn_backend="pallas")
    params = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    pool = jax.eval_shape(
        partial(lm.init_paged_pool, cfg, CELL_SLOTS, CELL_PAGES, PAGE))
    if tp == 1:
        repl = SingleDeviceSharding(v5e_2x2.devices[0])
        param_sh = jax.tree.map(lambda _: repl, params)
        pool_sh = jax.tree.map(lambda _: repl, pool)
        mesh_ctx = contextlib.nullcontext()
    else:
        mesh = shd.make_mesh((tp,), ("model",), devices=v5e_2x2.devices[:tp])
        repl = NamedSharding(mesh, P())
        param_sh = shd.serve_param_sharding_tree(params, mesh)
        pool_sh = shd.serve_pool_sharding_tree(pool, mesh)
        mesh_ctx = shd.use_mesh(shd.serving_context(mesh))
    place = lambda tree, shardings: jax.tree.map(
        lambda a, sh: _sds(sh, a.shape, a.dtype), tree, shardings)
    params, pool = place(params, param_sh), place(pool, pool_sh)
    arg = lambda shape, dtype=jnp.int32: _sds(repl, shape, dtype)
    if program == "decode":
        fn = partial(scheduler._decode_paged_fn, cfg=cfg)
        B = CELL_SLOTS
        args = (arg((B,)), arg((B,)), arg((B,), jnp.bool_), arg((B, CELL_NB)),
                arg((B, 2), jnp.uint32), arg((B,)), arg((B,), jnp.float32))
    else:
        fn = partial(scheduler._burst_prefill_fn, cfg=cfg, page_size=PAGE,
                     use_context=program == "prefill_with_context")
        W, T = 1, 256
        args = (arg((W, T)), arg((W, CELL_NB)), arg((W,)), arg((W,)),
                arg((W,)), arg((W, 2), jnp.uint32), arg((W,), jnp.float32))
    with mesh_ctx:
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pool, *args).compile()

    slab = (CELL_PAGES, PAGE, cfg.n_kv // tp, cfg.hd)
    shapes = {",".join(map(str, s))
              for s in (slab, (1,) + slab, (cfg.n_layers,) + slab)}
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    pool_ops = [
        (result.split("{")[0], op) for result, op in _INSTR.findall(hlo)
        if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                  "dynamic-update-slice")
        and any(f"bf16[{s}]" in result for s in shapes)
    ]
    assert pool_ops == []
    slab_bytes = 2 * CELL_PAGES * PAGE * (cfg.n_kv // tp) * cfg.hd
    assert slab_bytes == 67_117_056
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes
