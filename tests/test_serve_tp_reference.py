"""``Scheduler(tp=4)`` against the plain float32 reference.

The mistral-nemo-12b smoke config (heads * head_dim != d_model, as in
the published model; 4 KV heads, so each of 4 virtual devices holds one)
is served with seeded weights from ``bench/weights.py`` on a 4-way
serving mesh: column-parallel Q/K/V and up/gate projections, row-parallel
``wo`` and ``w_down`` ending in an all-reduce, the embedding and the LM
head split by vocabulary.  Prefill and then teacher-forced paged decode
give logits at every position after each prompt, which
``bench/reference.py`` (a plain float32 forward pass that imports
nothing of ``src/``) computes from the same seed.  The scheduler's own
greedy streams are judged by the reference too.

Tolerance: one bfloat16 ulp of the largest logit is 2^-8 * max|logit|.
A program that computes in float32 differs from the float32 reference
only by summation order (the row-parallel partial sums, the paged
attention's reductions) and rsqrt/exp rounding, each a few float32 ulps
(2^-24) through two layers; it must stay 16x inside one bf16 ulp:
``TOL = 2^-12 * max|logit|``.  The control computes the same program in
bfloat16 and has to fail it.
"""
import pytest
from conftest import run_in_8dev_subprocess

TOL_REL = 2.0 ** -12

_SNIPPET = r"""
import dataclasses, json, sys
sys.path.insert(0, ".")
from functools import partial
import jax, jax.numpy as jnp, numpy as np
from bench import reference, weights
from repro import configs
from repro.dist import sharding as shd
from repro.models import lm
from repro.serve.scheduler import Request, Scheduler

assert jax.device_count() == 4, jax.devices()
SEED = 2 ** 33 + 7
PAGE, MAX_LEN, N = 8, 64, 12
base = dataclasses.replace(configs.get_smoke_config("mistral-nemo-12b"),
                           cache_dtype="float32")
c = {"hidden_size": base.d_model, "intermediate_size": base.d_ff,
     "num_attention_heads": base.n_heads, "num_key_value_heads": base.n_kv,
     "head_dim": base.hd, "num_hidden_layers": base.n_layers,
     "vocab_size": base.vocab_size, "rope_theta": base.rope_theta,
     "rms_norm_eps": 1e-5, "tie_word_embeddings": base.tie_embeddings,
     "attention_bias": base.qkv_bias}
V = c["vocab_size"]
rng = np.random.default_rng(0)
P = [24, 17, 30, 9]
prompts = [rng.integers(1, V, p).astype(np.int32) for p in P]
conts = [rng.integers(1, V, N).astype(np.int32) for _ in P]
every_id = lambda s: np.tile(np.arange(V, dtype=np.int32), (len(s), 1))
seqs = [np.concatenate([p, q]) for p, q in zip(prompts, conts)]
ref = [rd[p - 1:p - 1 + N] for (_, _, rd), p in zip(
    reference.logit_reads(c, SEED, seqs, [every_id(s) for s in seqs],
                          dtype=jnp.float32, pad_to=8), P)]


def run(cfg):
    shapes = jax.eval_shape(partial(lm.init, cfg=cfg), jax.random.PRNGKey(0))
    params = weights.program_params(c, shapes, SEED)
    sched = Scheduler(cfg, params, tp=4, max_slots=4, max_len=MAX_LEN,
                      page_size=PAGE, prefill_buckets=[32])
    B, nb = len(P), MAX_LEN // PAGE
    bt = jnp.arange(1, 1 + B * nb, dtype=jnp.int32).reshape(B, nb)
    pool = lm.init_paged_pool(cfg, B, B * nb + 1, PAGE, mesh=sched.mesh)
    tok = np.zeros((B, 32), np.int32)
    for i, p in enumerate(prompts):
        tok[i, :len(p)] = p
    prefill = jax.jit(lambda p, pool, t: lm.prefill_paged(
        p, {"tokens": t}, cfg, pool, bt, jnp.arange(B, dtype=jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.asarray(P, jnp.int32), PAGE, False))
    decode = jax.jit(lambda p, pool, t, pos: lm.decode_step_paged(
        p, {"tokens": t[:, None]}, pos, pool, bt, cfg))
    got = []
    with shd.use_mesh(sched.mesh_ctx):
        pool, lg = prefill(sched.params, pool, jnp.asarray(tok))
        got.append(np.asarray(lg[:, -1], np.float32))
        pos = np.array(P, np.int32)
        for j in range(N - 1):
            cur = jnp.asarray([q[j] for q in conts], jnp.int32)
            lg, pool = decode(sched.params, pool, cur, jnp.asarray(pos))
            got.append(np.asarray(lg[:, -1], np.float32))
            pos += 1
    got = np.stack(got, 1)                      # (B, N, V)
    served = sched.serve([Request(prompt=p, n_tokens=N, rid=i)
                          for i, p in enumerate(prompts)])
    gaps = reference.served_gaps(c, SEED, prompts,
                                 [r.generated.astype(np.int32) for r in served],
                                 dtype=jnp.float32)
    leaves = jax.tree.leaves(sched.params)
    return {"max_diff": max(float(np.abs(g - r).max()) for g, r in zip(got, ref)),
            "max_abs": max(float(np.abs(r).max()) for r in ref),
            "max_served_gap": max(float(g.max()) for g in gaps),
            "param_bytes_per_chip": sched.param_bytes_per_chip,
            "param_bytes": sum(int(x.nbytes) for x in leaves),
            "replicated_bytes": sum(int(x.nbytes) for x in leaves
                                    if x.sharding.is_fully_replicated)}


print(json.dumps({"variant": "float32", **run(base)}))
print(json.dumps({"variant": "bfloat16", **run(dataclasses.replace(
    base, compute_dtype="bfloat16", cache_dtype="bfloat16"))}))
"""


@pytest.fixture(scope="module")
def readings():
    recs = run_in_8dev_subprocess(_SNIPPET, timeout=240, n_devices=4)
    return {r["variant"]: r for r in recs}


def test_tp4_logits_match_reference(readings):
    r = readings["float32"]
    assert r["max_diff"] <= TOL_REL * r["max_abs"], r
    # Greedy tokens the scheduler served: each one's reference logit is
    # within the same tolerance of the reference's best.
    assert r["max_served_gap"] <= TOL_REL * r["max_abs"], r


def test_bf16_compute_fails_the_tolerance(readings):
    r = readings["bfloat16"]
    assert r["max_diff"] > TOL_REL * r["max_abs"], r


def test_param_bytes_per_chip_counts_one_quarter(readings):
    """The counter reads the placed shards: every matrix and the
    vocabulary split 4 ways, the norms whole on each device."""
    r = readings["float32"]
    sharded = r["param_bytes"] - r["replicated_bytes"]
    assert r["param_bytes_per_chip"] == sharded // 4 + r["replicated_bytes"], r
    assert r["replicated_bytes"] < r["param_bytes"] // 100, r
