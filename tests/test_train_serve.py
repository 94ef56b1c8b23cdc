"""Training substrate + serving engine tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, make_batch
from repro.train import (
    AsyncCheckpointer,
    OptConfig,
    init_train_state,
    latest_step,
    load_checkpoint,
    make_train_step,
    plan_mesh_shape,
    restore_tree,
    save_checkpoint,
)
from repro.train.compression import compressed_psum
from repro.data.pipeline import TokenPipeline


OPT = OptConfig(lr=1e-3, warmup_steps=2, total_steps=20, grad_clip=1.0)


def _setup(name="olmo-1b", seed=0):
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    params, opt_state = init_train_state(model, jax.random.PRNGKey(seed))
    step = jax.jit(make_train_step(model, OPT))
    rng = np.random.default_rng(seed)
    batch = make_batch(cfg, rng, batch=2, seq=32)
    return cfg, model, params, opt_state, step, batch


# ------------------------------------------------------------------ training
def test_loss_decreases_over_steps():
    cfg, model, params, opt_state, step, batch = _setup()
    losses = []
    for _ in range(8):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(losses))


def test_grad_clip_bounds_update():
    cfg, model, params, opt_state, step, batch = _setup()
    _, _, m = step(params, opt_state, batch)
    assert float(m["grad_norm"]) >= 0
    assert float(m["lr"]) <= OPT.lr


# --------------------------------------------------------------- checkpoints
def test_checkpoint_roundtrip_and_resume(tmp_path):
    cfg, model, params, opt_state, step, batch = _setup()
    for _ in range(3):
        params, opt_state, _ = step(params, opt_state, batch)
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 3, {"params": params, "opt": opt_state})
    assert latest_step(d) == 3
    s, flat = load_checkpoint(d)
    restored = restore_tree({"params": params, "opt": opt_state}, flat)
    # identical continue: one more step from both must agree exactly
    p1, o1, m1 = step(params, opt_state, batch)
    p2, o2, m2 = step(restored["params"], restored["opt"], batch)
    assert float(m1["loss"]) == float(m2["loss"])


def test_checkpoint_keep_and_atomicity(tmp_path):
    d = str(tmp_path / "ckpt")
    tree = {"a": jnp.arange(10.0)}
    for s in range(5):
        save_checkpoint(d, s, tree, keep=2)
    kept = sorted(x for x in os.listdir(d) if x.startswith("step_"))
    assert kept == ["step_00000003", "step_00000004"]
    assert latest_step(d) == 4


def test_async_checkpointer(tmp_path):
    d = str(tmp_path / "ckpt")
    ck = AsyncCheckpointer(d, keep=2)
    ck.save(1, {"x": jnp.ones(4)})
    ck.save(2, {"x": jnp.ones(4) * 2})  # waits for save 1
    ck.wait()
    assert latest_step(d) == 2
    _, flat = load_checkpoint(d)
    np.testing.assert_array_equal(flat["x"], np.ones(4) * 2)


def test_resharding_restore_changes_sharding(tmp_path):
    # checkpoint saved "on one mesh" restores under any sharding spec
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    d = str(tmp_path / "ckpt")
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(d, 0, tree)
    _, flat = load_checkpoint(d)
    mesh = Mesh(np.array(jax.devices()).reshape(1), ("data",))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored = restore_tree(tree, flat, shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


# ------------------------------------------------------------------- elastic
def test_plan_mesh_shape_shrink():
    full = plan_mesh_shape(512, model_parallel=16, chips_per_pod=256)
    assert (full.pods, full.data, full.model) == (2, 16, 16)
    # lose one pod minus a few chips
    degraded = plan_mesh_shape(250, model_parallel=16, chips_per_pod=256)
    assert degraded.pods == 1 and degraded.model == 16
    assert degraded.chips_used == degraded.data * 16 <= 250
    with pytest.raises(ValueError):
        plan_mesh_shape(8, model_parallel=16)


# ----------------------------------------------------------------- pipeline
def test_pipeline_deterministic_and_resumable():
    p1 = TokenPipeline(vocab=100, batch=4, seq=8, seed=7)
    a = p1.next_batch()
    b = p1.next_batch()
    state = p1.state_dict()
    c = p1.next_batch()
    p2 = TokenPipeline(vocab=100, batch=4, seq=8, seed=7)
    p2.load_state_dict(state)
    np.testing.assert_array_equal(p2.next_batch(), c)
    # shards are disjoint streams
    s0 = TokenPipeline(vocab=100, batch=4, seq=8, seed=7, shard=0, num_shards=2)
    s1 = TokenPipeline(vocab=100, batch=4, seq=8, seed=7, shard=1, num_shards=2)
    assert not np.array_equal(s0.next_batch(), s1.next_batch())


def test_pipeline_prefetch():
    p = TokenPipeline(vocab=50, batch=2, seq=4, seed=1, prefetch=3)
    direct = [p.batch_at(i) for i in range(3)]
    p.start()
    got = [p.next_prefetched() for _ in range(3)]
    p.stop()
    for d, g in zip(direct, got):
        np.testing.assert_array_equal(d, g)


# ------------------------------------------------------------- compression
def test_compressed_psum_error_feedback():
    # single participant: compressed_psum must converge to the true sum via
    # error feedback (residual telescopes)
    import jax

    from jax import shard_map

    def step(g, r):
        return shard_map(
            lambda gg, rr: compressed_psum(gg, rr, "x"),
            mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]), ("x",)),
            in_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
            out_specs=(jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        )(g, r)

    g = jnp.asarray(np.random.default_rng(0).normal(size=64).astype(np.float32))
    r = jnp.zeros_like(g)
    acc_true = np.zeros(64, np.float64)
    acc_comp = np.zeros(64, np.float64)
    for _ in range(50):
        out, r = step(g, r)
        acc_true += np.asarray(g, np.float64)
        acc_comp += np.asarray(out, np.float64)
    # accumulated compressed sum tracks the true sum (error feedback works)
    rel = np.linalg.norm(acc_comp - acc_true) / np.linalg.norm(acc_true)
    assert rel < 0.01, rel


# -------------------------------------------------------------------- serve
@pytest.mark.parametrize("name", ["olmo-1b", "gemma3-4b", "rwkv6-3b", "zamba2-2.7b", "qwen2-moe-a2.7b"])
def test_decode_matches_forward_teacher_forcing(name):
    """Step-by-step decode must reproduce the parallel forward's logits —
    validates KV caches and recurrent states exactly."""
    cfg = get_config(name).reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    b, l = 2, 12
    tokens = rng.integers(0, cfg.vocab, size=(b, l)).astype(np.int32)

    # parallel forward hidden -> per-position logits
    kw = dict(dtype=jnp.float32, remat=False)
    if cfg.family == "moe":
        kw["capacity_factor"] = 8.0
    out = model.forward_hidden(params, {"tokens": jnp.asarray(tokens)}, **kw)
    h = out[0] if isinstance(out, tuple) else out
    if cfg.family == "ssm":  # rwkv: untied head
        table = params["lm_head"]["w"].T
    else:
        from repro.models.transformer import logits_table

        table = logits_table(cfg, params)
    ref_logits = np.asarray(h @ table.T.astype(h.dtype), np.float32)  # [B, L, V]

    # sequential decode over the same tokens
    cache = model.init_cache(b, 32, dtype=jnp.float32)
    got = []
    dkw = dict(dtype=jnp.float32)
    if cfg.family == "moe":
        dkw["capacity_factor"] = 8.0
    for t in range(l):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray(tokens[:, t : t + 1]), jnp.int32(t), **dkw
        )
        got.append(np.asarray(logits, np.float32))
    got = np.stack(got, axis=1)  # [B, L, V]
    np.testing.assert_allclose(got, ref_logits, rtol=2e-3, atol=2e-3)


def test_greedy_generate_and_bucket_server():
    from repro.serve import BucketServer, Request, greedy_generate

    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(5))
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, cfg.vocab, size=(2, 8)).astype(np.int32)
    out = greedy_generate(model, params, prompts, max_new=5, dtype=jnp.float32)
    assert out.shape == (2, 5)
    # determinism
    out2 = greedy_generate(model, params, prompts, max_new=5, dtype=jnp.float32)
    np.testing.assert_array_equal(out, out2)

    server = BucketServer(model, params, max_batch=4, dtype=jnp.float32)
    for i in range(3):
        server.submit(Request(uid=i, prompt=prompts[i % 2], max_new=4))
    done = server.drain()
    assert sorted(c.uid for c in done) == [0, 1, 2]
    # batched result equals solo result for the same prompt
    solo = greedy_generate(model, params, prompts[:1], max_new=4, dtype=jnp.float32)
    batched = next(c for c in done if c.uid == 0)
    np.testing.assert_array_equal(batched.tokens, solo[0])


def test_fast_prefill_matches_scan_prefill():
    """transformer.prefill (parallel) must fill the KV cache identically to
    token-by-token scan_prefill — same logits now and one step later."""
    from repro.models.transformer import prefill
    from repro.serve import scan_prefill

    cfg = get_config("gemma3-4b").reduced()  # exercises local/global layers
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(11))
    rng = np.random.default_rng(11)
    b, l = 2, 10
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (b, l)), jnp.int32)

    cache_a = model.init_cache(b, 32, dtype=jnp.float32)
    logits_a, cache_a = prefill(cfg, params, prompts, cache_a, dtype=jnp.float32)
    cache_b = model.init_cache(b, 32, dtype=jnp.float32)
    logits_b, cache_b = scan_prefill(model, params, cache_b, prompts, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                               rtol=2e-4, atol=2e-4)
    # continue one decode step from both caches
    nxt = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
    la, _ = model.decode_step(params, cache_a, nxt, jnp.int32(l), dtype=jnp.float32)
    lb, _ = model.decode_step(params, cache_b, nxt, jnp.int32(l), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=2e-4, atol=2e-4)
