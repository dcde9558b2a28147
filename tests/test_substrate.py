"""Optimizers, data pipeline, checkpointing, fault tolerance, grad comp."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import BitmapIndex, DataPipeline, PipelineState, SyntheticCorpus
from repro.models import transformer as T
from repro.optim import adamw, adafactor, adamw8bit, cosine_schedule


def _quad_problem(opt, steps=200, lr=0.05):
    """Minimize ||x - t||^2 with each optimizer; all must converge."""
    t = jnp.asarray(np.random.default_rng(0).normal(size=(256, 256)), jnp.float32)
    params = {"w": jnp.zeros((256, 256), jnp.float32)}
    state = opt.init(params)

    @jax.jit
    def step(params, state, i):
        g = jax.grad(lambda p: jnp.mean((p["w"] - t) ** 2))(params)
        upd, state = opt.update(g, state, params, i)
        params = jax.tree.map(lambda p, u: p - u, params, upd)
        return params, state

    for i in range(steps):
        params, state = step(params, state, i)
    return float(jnp.mean((params["w"] - t) ** 2))


def test_adamw_converges():
    assert _quad_problem(adamw(0.05, wd=0.0)) < 1e-2


def test_adafactor_converges():
    assert _quad_problem(adafactor(0.05)) < 1e-2


def test_adamw8bit_converges():
    assert _quad_problem(adamw8bit(0.05, wd=0.0)) < 1e-2


def test_adafactor_state_is_factored():
    opt = adafactor(1e-3)
    params = {"w": jnp.zeros((512, 256)), "b": jnp.zeros((17,))}
    st = opt.init(params)
    assert set(st["w"].keys()) == {"vr", "vc"}
    assert st["w"]["vr"].shape == (512,) and st["w"]["vc"].shape == (256,)
    assert set(st["b"].keys()) == {"v"}          # small vectors unfactored


def test_cosine_schedule():
    lr = cosine_schedule(1.0, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 1e-6
    assert float(lr(100)) <= 0.11


# ------------------------------------------------------------------ data
def test_bitmap_index_query_matches_numpy():
    corpus = SyntheticCorpus(n_docs=50_000, vocab=1000, seed=3)
    idx = BitmapIndex(corpus)
    got = idx.query("lang=2&quality>=3&!dedup_dup").to_array()
    want = np.nonzero((corpus.lang == 2) & (corpus.quality >= 3)
                      & ~corpus.dedup_dup)[0]
    np.testing.assert_array_equal(got, want)
    got2 = idx.query("lang=0|lang=1").to_array()
    want2 = np.nonzero(corpus.lang <= 1)[0]
    np.testing.assert_array_equal(got2, want2)


def test_pipeline_determinism_and_restart():
    corpus = SyntheticCorpus(n_docs=2000, vocab=1000, seed=1, mean_len=100)
    idx = BitmapIndex(corpus)
    mk = lambda st: DataPipeline(idx, st, batch=4, seq_len=256)
    p1 = mk(PipelineState(query="quality>=1", seed=7))
    stream1 = [p1.next_batch()[0] for _ in range(6)]
    # replay from a mid-stream snapshot
    p2 = mk(PipelineState(query="quality>=1", seed=7))
    for _ in range(3):
        p2.next_batch()
    snap = p2.state.to_dict()
    p3 = mk(PipelineState.from_dict(snap))
    for i in range(3, 6):
        np.testing.assert_array_equal(p3.next_batch()[0], stream1[i])


def test_pipeline_shards_are_disjoint():
    corpus = SyntheticCorpus(n_docs=5000, vocab=1000, seed=2, mean_len=200)
    idx = BitmapIndex(corpus)
    a = DataPipeline(idx, PipelineState(query="quality>=0", seed=5),
                     batch=2, seq_len=128, n_shards=2, shard_id=0)
    b = DataPipeline(idx, PipelineState(query="quality>=0", seed=5),
                     batch=2, seq_len=128, n_shards=2, shard_id=1)
    ta = a.next_batch()[0]
    tb = b.next_batch()[0]
    assert not np.array_equal(ta, tb)


# ------------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import save_checkpoint, restore_checkpoint
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 4), jnp.bfloat16),
                  "d": jnp.asarray(3, jnp.int32)}}
    save_checkpoint(str(tmp_path), 7, tree, extra={"rng": 123})
    like = jax.tree.map(jnp.zeros_like, tree)
    got, extra, step = restore_checkpoint(str(tmp_path), like)
    assert step == 7 and extra == {"rng": 123}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_fault_tolerant_training_resumes(tmp_path):
    """Injected failures mid-run; final state must equal the failure-free run."""
    from repro.optim import adamw
    from repro.runtime import ResilientTrainer, simulate_failure
    from repro.train import TrainState, make_train_step

    cfg = get_config("stablelm-1.6b", reduced=True)
    rng = jax.random.PRNGKey(0)
    params = T.init_lm(rng, cfg)
    opt = adamw(1e-3)
    step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=())

    def batches(step):
        r = np.random.default_rng(step)
        toks = r.integers(0, cfg.vocab, (2, 33)).astype(np.int32)
        return {"tokens": jnp.asarray(toks),
                "mask": jnp.ones((2, 33), jnp.float32)}

    def run(failures, ckdir):
        state = TrainState(params, opt.init(params), 0)
        tr = ResilientTrainer(step_fn, ckdir, ckpt_every=4,
                              failure_source=simulate_failure(failures))
        state, _ = tr.run(state, batches, n_steps=10)
        return state, tr

    clean, _ = run(set(), str(tmp_path / "clean"))
    faulty, tr = run({3, 7}, str(tmp_path / "faulty"))
    assert tr.restarts == 2
    assert int(faulty["step"]) == 10
    for a, b in zip(jax.tree.leaves(clean["params"]),
                    jax.tree.leaves(faulty["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_straggler_monitor():
    from repro.runtime import HeartbeatMonitor, StragglerPolicy
    mon = HeartbeatMonitor(StragglerPolicy(factor=2.0))
    for _ in range(10):
        mon.beat(0.1)
    assert mon.beat(0.5) is True
    assert mon.stragglers == 1
    assert mon.beat(0.1) is False


# ------------------------------------------------------------------ grad comp
def test_grad_compression_roundtrip():
    from repro.grad_comp import compress_leaf, decompress_leaf, compression_ratio
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(1000, 257)), jnp.float32)
    k = 1024
    c = compress_leaf(g, k)
    back = decompress_leaf(c, g.shape, g.dtype)
    # exact on the top-k coordinates, zero elsewhere
    flat = np.asarray(g).reshape(-1)
    idx = np.argsort(-np.abs(flat))[:k]
    bflat = np.asarray(back).reshape(-1)
    np.testing.assert_allclose(bflat[idx], flat[idx], rtol=1e-6)
    zero_idx = np.setdiff1d(np.arange(flat.size), idx)
    assert np.abs(bflat[zero_idx]).max() == 0.0
    assert compression_ratio(c, flat.size) < 0.05


def test_grad_compression_clustered_indices_use_bitmap_containers():
    """Hot-region gradients produce bitmap containers (better than 16b/idx)."""
    from repro.grad_comp import compress_leaf, compression_ratio
    g = np.zeros(300_000, np.float32)
    g[10_000:18_192] = np.random.default_rng(1).normal(size=8192) + 5
    c = compress_leaf(jnp.asarray(g), 8192)
    kinds = np.asarray(c.slab.kinds)
    assert (kinds == 2).sum() >= 1        # dense chunk -> bitmap container
    assert compression_ratio(c, g.size) < 0.06


def test_compressed_crosspod_mean_matches_dense_topk():
    """shard_map over a fake 2-pod mesh: compressed mean == mean of top-k."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.grad_comp import compressed_crosspod_mean

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices (run under dryrun env)")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("pod",))
    g = jnp.asarray(np.random.default_rng(2).normal(size=(2, 4096)), jnp.float32)

    def f(gl):
        return compressed_crosspod_mean({"w": gl[0]}, axis_name="pod",
                                        ratio=0.1)["w"]

    out = shard_map(f, mesh=mesh, in_specs=(P("pod"),), out_specs=P())(g)
    assert np.isfinite(np.asarray(out)).all()


def test_leaf_overlap_and_jaccard():
    """Compressed-leaf index overlap via the cardinality-only dispatch path."""
    from repro.grad_comp import compress_leaf, leaf_jaccard, leaf_overlap
    g1 = jnp.asarray(np.random.default_rng(0).normal(size=8192), jnp.float32)
    g2 = jnp.asarray(g1).at[:4096].set(0.0)
    c1, c2 = compress_leaf(g1, 512), compress_leaf(g2, 512)
    i1 = set(np.asarray(jnp.sort(jnp.argsort(-jnp.abs(g1))[:512])).tolist())
    i2 = set(np.asarray(jnp.sort(jnp.argsort(-jnp.abs(g2))[:512])).tolist())
    assert int(leaf_overlap(c1, c2)) == len(i1 & i2)
    want_j = len(i1 & i2) / len(i1 | i2)
    assert float(leaf_jaccard(c1, c2)) == pytest.approx(want_j, rel=1e-6)
