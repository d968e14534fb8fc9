"""Fused paged-attention kernels: bitwise parity against the XLA
gather+attend reference (interpret mode), dispatch semantics of the
``AttnBackend`` enum, and end-to-end greedy token-exactness across all
served families with the Pallas backend forced in interpret mode.

Parity is asserted with ``assert_array_equal`` — the kernels keep the
reference's exact compute structure (single-normalization softmax, one
dot-general per contraction), so any drift at all is a bug, not a
tolerance question."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops

VOCAB = 512


def _paged_kv(rng, n_pages, page, Hk, hd, hdv, dtype=jnp.bfloat16):
    k = jnp.asarray(rng.standard_normal((n_pages, page, Hk, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((n_pages, page, Hk, hdv)), dtype)
    return k, v


def _block_table(rng, B, nb, n_pages):
    """Distinct non-garbage pages per (slot, idx) — page 0 is reserved."""
    ids = rng.permutation(np.arange(1, n_pages))[: B * nb]
    return jnp.asarray(ids.reshape(B, nb), jnp.int32)


# ============================ decode: GQA ====================================
class TestPagedDecodeGQA:
    @pytest.mark.parametrize("B,Hk,G,hd,hdv,page,nb", [
        (1, 1, 1, 8, 8, 4, 1),       # minimal
        (3, 2, 4, 16, 16, 8, 3),     # GQA broadcast, several pages
        (2, 2, 1, 16, 8, 8, 2),      # MQA-ish, hdv != hd
        (4, 1, 6, 32, 32, 16, 2),    # wide groups
    ])
    def test_bitwise_vs_xla(self, B, Hk, G, hd, hdv, page, nb):
        rng = np.random.default_rng(B * 100 + nb)
        n_pages = 1 + B * nb + 3     # spare pages the tables never touch
        kp, vp = _paged_kv(rng, n_pages, page, Hk, hd, hdv)
        bt = _block_table(rng, B, nb, n_pages)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = jnp.asarray(rng.integers(0, nb * page, B), jnp.int32)
        want = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                    backend="xla")
        got = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                   backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_garbage_page_rows(self):
        """Inactive slots point every table entry at the reserved page 0
        with pos clamped to 0 — the kernel must mask exactly like the
        reference (only position 0 attended, out of garbage data)."""
        rng = np.random.default_rng(7)
        B, Hk, G, hd, page, nb = 3, 2, 2, 16, 8, 2
        n_pages = 1 + B * nb
        kp, vp = _paged_kv(rng, n_pages, page, Hk, hd, hd)
        bt = np.array(_block_table(rng, B, nb, n_pages))
        bt[1] = 0                                      # inactive slot
        bt = jnp.asarray(bt)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = jnp.asarray([5, 0, nb * page - 1], jnp.int32)
        want = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                    backend="xla")
        got = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                   backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=15, deadline=None)
    @given(
        B=st.integers(1, 4), nb=st.integers(1, 4),
        page=st.sampled_from([4, 8]), G=st.integers(1, 4),
        seed=st.integers(0, 2**16),
    )
    def test_parity_property(self, B, nb, page, G, seed):
        """Ragged pos (including 0 and the last slot position) and
        non-power-of-two page-pool sizes never break parity."""
        rng = np.random.default_rng(seed)
        Hk, hd = 2, 8
        n_pages = 1 + B * nb + int(rng.integers(0, 3))   # often non-pow2
        kp, vp = _paged_kv(rng, n_pages, page, Hk, hd, hd)
        bt = _block_table(rng, B, nb, n_pages)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = np.asarray(rng.integers(0, nb * page, B), np.int32)
        pos[0] = 0                                      # fresh slot edge
        pos[-1] = nb * page - 1                         # full slot edge
        pos = jnp.asarray(pos)
        want = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                    backend="xla")
        got = ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                   backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("layer", [0, 2, 4])
    def test_layer_index(self, layer):
        """The kernel reads slab ``layer`` of a stacked pool whose groups
        hold different data: it equals the XLA backend on the same pool
        bitwise, and both equal the slab alone read as layer 0."""
        rng = np.random.default_rng(11 + layer)
        groups, B, Hk, G, hd, page, nb = 5, 3, 2, 4, 16, 8, 3
        n_pages = 1 + B * nb + 2
        kp, vp = _paged_kv(rng, groups * n_pages, page, Hk, hd, hd)
        kp = kp.reshape(groups, n_pages, page, Hk, hd)
        vp = vp.reshape(groups, n_pages, page, Hk, hd)
        bt = _block_table(rng, B, nb, n_pages)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = jnp.asarray([0, 13, nb * page - 1], jnp.int32)
        ly = jnp.int32(layer)
        want = ops.paged_decode_gqa(q, kp, vp, bt, pos, ly, backend="xla")
        got = ops.paged_decode_gqa(q, kp, vp, bt, pos, ly,
                                   backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        alone = ops.paged_decode_gqa(q, kp[layer][None], vp[layer][None], bt,
                                     pos, 0, backend="xla")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(alone))


# ============================ decode: MLA ====================================
class TestPagedDecodeMLA:
    @pytest.mark.parametrize("B,H,r,dr,page,nb", [
        (1, 1, 8, 4, 4, 1),
        (3, 4, 32, 16, 8, 3),
        (2, 8, 64, 32, 8, 2),
    ])
    def test_bitwise_vs_xla(self, B, H, r, dr, page, nb):
        rng = np.random.default_rng(B * 10 + H)
        n_pages = 1 + B * nb + 2
        cp = jnp.asarray(rng.standard_normal((n_pages, page, r)), jnp.bfloat16)
        rp = jnp.asarray(rng.standard_normal((n_pages, page, dr)), jnp.bfloat16)
        bt = _block_table(rng, B, nb, n_pages)
        qa = jnp.asarray(rng.standard_normal((B, 1, H, r)), jnp.float32)
        qr = jnp.asarray(rng.standard_normal((B, 1, H, dr)), jnp.float32)
        pos = jnp.asarray(rng.integers(0, nb * page, B), jnp.int32)
        scale = 1.0 / np.sqrt(r + dr)
        want = ops.paged_decode_mla(qa, qr, cp[None], rp[None], bt, pos, 0,
                                    scale, backend="xla")
        got = ops.paged_decode_mla(qa, qr, cp[None], rp[None], bt, pos, 0,
                                   scale, backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=10, deadline=None)
    @given(B=st.integers(1, 3), nb=st.integers(1, 3),
           seed=st.integers(0, 2**16))
    def test_parity_property(self, B, nb, seed):
        rng = np.random.default_rng(seed)
        H, r, dr, page = 2, 16, 8, 4
        n_pages = 1 + B * nb + int(rng.integers(0, 2))
        cp = jnp.asarray(rng.standard_normal((n_pages, page, r)), jnp.bfloat16)
        rp = jnp.asarray(rng.standard_normal((n_pages, page, dr)), jnp.bfloat16)
        bt = _block_table(rng, B, nb, n_pages)
        qa = jnp.asarray(rng.standard_normal((B, 1, H, r)), jnp.float32)
        qr = jnp.asarray(rng.standard_normal((B, 1, H, dr)), jnp.float32)
        pos = np.asarray(rng.integers(0, nb * page, B), np.int32)
        pos[0] = 0
        want = ops.paged_decode_mla(qa, qr, cp[None], rp[None], bt,
                                    jnp.asarray(pos), 0, 0.125, backend="xla")
        got = ops.paged_decode_mla(qa, qr, cp[None], rp[None], bt,
                                   jnp.asarray(pos), 0, 0.125,
                                   backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ===================== prefill: [ctx ; causal tail] ==========================
class TestPrefixPrefill:
    @pytest.mark.parametrize("B,T,Hk,G,hd,L", [
        (1, 1, 1, 1, 8, 0),          # single token, no context
        (3, 7, 2, 4, 16, 16),        # T not a multiple of the q tile
        (2, 8, 2, 1, 16, 24),        # tile-aligned T, bigger context
        (2, 5, 1, 3, 8, 8),
    ])
    def test_bitwise_vs_xla(self, B, T, Hk, G, hd, L):
        rng = np.random.default_rng(B + T + L)
        q = jnp.asarray(rng.standard_normal((B, T, Hk * G, hd)), jnp.float32)
        kt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        vt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        if L:
            kc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
            vc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
            ctx = np.asarray(rng.integers(0, L + 1, B), np.int32)
            ctx[0] = 0                               # no-hit burst member
            ctx[-1] = L                              # fully valid context
        else:
            kc = vc = None
            ctx = np.zeros(B, np.int32)
        ctx = jnp.asarray(ctx)
        want = ops.prefix_prefill(q, kc, vc, kt, vt, ctx, backend="xla")
        got = ops.prefix_prefill(q, kc, vc, kt, vt, ctx,
                                 backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_bf16_context_pages(self):
        """Serving gathers bf16 context pages cast to the compute dtype
        before attending; parity must hold on that exact input too."""
        rng = np.random.default_rng(3)
        B, T, Hk, G, hd, L = 2, 4, 2, 2, 16, 16
        q = jnp.asarray(rng.standard_normal((B, T, Hk * G, hd)), jnp.float32)
        mk = lambda s, d: jnp.asarray(rng.standard_normal(s), d)
        kc = mk((B, L, Hk, hd), jnp.bfloat16).astype(jnp.float32)
        vc = mk((B, L, Hk, hd), jnp.bfloat16).astype(jnp.float32)
        kt = mk((B, T, Hk, hd), jnp.float32)
        vt = mk((B, T, Hk, hd), jnp.float32)
        ctx = jnp.asarray([7, L], jnp.int32)
        want = ops.prefix_prefill(q, kc, vc, kt, vt, ctx, backend="xla")
        got = ops.prefix_prefill(q, kc, vc, kt, vt, ctx,
                                 backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=15, deadline=None)
    @given(B=st.integers(1, 3), T=st.integers(1, 12),
           L=st.sampled_from([0, 8, 16]), seed=st.integers(0, 2**16))
    def test_parity_property(self, B, T, L, seed):
        rng = np.random.default_rng(seed)
        Hk, G, hd = 2, 2, 8
        q = jnp.asarray(rng.standard_normal((B, T, Hk * G, hd)), jnp.float32)
        kt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        vt = jnp.asarray(rng.standard_normal((B, T, Hk, hd)), jnp.float32)
        if L:
            kc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
            vc = jnp.asarray(rng.standard_normal((B, L, Hk, hd)), jnp.float32)
        else:
            kc = vc = None
        ctx = jnp.asarray(rng.integers(0, L + 1, B), jnp.int32)
        want = ops.prefix_prefill(q, kc, vc, kt, vt, ctx, backend="xla")
        got = ops.prefix_prefill(q, kc, vc, kt, vt, ctx,
                                 backend="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ============================== dispatch =====================================
class TestBackendDispatch:
    def test_resolve(self):
        on_tpu = jax.default_backend() == "tpu"
        want_auto = ops.AttnBackend.PALLAS if on_tpu else ops.AttnBackend.XLA
        assert ops.resolve_attn_backend() is want_auto
        assert ops.resolve_attn_backend("auto") is want_auto
        assert ops.resolve_attn_backend("xla") is ops.AttnBackend.XLA
        assert ops.resolve_attn_backend("pallas") is ops.AttnBackend.PALLAS
        assert (ops.resolve_attn_backend("pallas_interpret")
                is ops.AttnBackend.PALLAS_INTERPRET)
        with pytest.raises(ValueError):
            ops.resolve_attn_backend("cudnn")

    @pytest.mark.skipif(jax.default_backend() == "tpu",
                        reason="auto resolves to the Pallas kernel on TPU")
    def test_auto_avoids_pallas_off_tpu(self):
        """The default backend must never pay interpreter overhead on
        CPU: the traced decode program contains no pallas_call."""
        rng = np.random.default_rng(0)
        B, Hk, G, hd, page, nb = 2, 1, 2, 8, 4, 2
        n_pages = 1 + B * nb
        kp, vp = _paged_kv(rng, n_pages, page, Hk, hd, hd)
        bt = _block_table(rng, B, nb, n_pages)
        q = jnp.asarray(rng.standard_normal((B, 1, Hk * G, hd)), jnp.float32)
        pos = jnp.asarray([1, 3], jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda *a: ops.paged_decode_gqa(*a)
        )(q, kp[None], vp[None], bt, pos, 0)
        assert "pallas_call" not in str(jaxpr)
        np.testing.assert_array_equal(
            np.asarray(ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0)),
            np.asarray(ops.paged_decode_gqa(q, kp[None], vp[None], bt, pos, 0,
                                            backend="xla")),
        )

    def test_config_validates_backend(self):
        from repro import configs
        cfg = configs.get_smoke_config("qwen2.5-3b")
        for b in ("auto", "xla", "pallas", "pallas_interpret"):
            dataclasses.replace(cfg, attn_backend=b).validate()
        with pytest.raises(AssertionError):
            dataclasses.replace(cfg, attn_backend="cuda").validate()


# ====================== end-to-end serving exactness =========================
# Keep this list in sync with tests/test_archs_smoke.py::CONSISTENCY_ARCHS.
SERVED_ARCHS = [
    "qwen2.5-3b", "phi4-mini-3.8b", "mistral-nemo-12b", "musicgen-large",
    "falcon-mamba-7b", "jamba-v0.1-52b", "deepseek-v3-671b",
    "moonshot-v1-16b-a3b",
]


class TestServingExactnessPallas:
    @pytest.mark.parametrize("arch", SERVED_ARCHS)
    def test_greedy_exact_with_pallas_interpret(self, arch):
        """Every served family produces bit-identical greedy tokens with
        the fused kernels forced (interpret mode on CPU) vs per-request
        ``Engine.generate`` on the monolithic XLA path — the end-to-end
        form of the per-kernel parity assertions above."""
        from repro import configs
        from repro.models import lm
        from repro.serve import Engine, Request, Scheduler

        cfg = configs.get_smoke_config(arch)
        params = lm.init(jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, params, max_len=32)
        sched = Scheduler(cfg, params, max_slots=2, max_len=32, page_size=8,
                          attn_backend="pallas_interpret")
        assert sched.cfg.attn_backend == "pallas_interpret"
        rng = np.random.default_rng(2)
        reqs = [
            Request(prompt=rng.integers(0, VOCAB, n).astype(np.int32),
                    n_tokens=t)
            for n, t in [(3, 3), (6, 2), (9, 3)]
        ]
        for req, res in zip(reqs, sched.serve(reqs)):
            ref = eng.generate(
                req.prompt[None], n_tokens=req.n_tokens, request_ids=[res.rid]
            )
            np.testing.assert_array_equal(ref.tokens[0], res.tokens)
