"""Substrate layers: optimizers, checkpoint, data determinism, distributed
helpers (compression, straggler, elastic planner, sharding rules)."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.distributed import compression as comp
from repro.distributed import sharding as sh
from repro.distributed.resilience import ElasticPlanner, StragglerMonitor
from repro.optim import (
    clip_by_global_norm,
    global_norm,
    linear_warmup_cosine,
    make_optimizer,
)


# --- optimizers -------------------------------------------------------------


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_descends_quadratic(name):
    init, update = make_optimizer(name, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0]), "m": jnp.ones((4, 6)) * 2}
    state = init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["m"] ** 2)

    l0 = float(loss(params))
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, state = update(g, state, params, 5e-2)
    assert float(loss(params)) < 0.05 * l0


def test_adafactor_state_is_factored():
    init, _ = make_optimizer("adafactor")
    params = {"mat": jnp.ones((8, 16)), "vec": jnp.ones((5,)),
              "t3": jnp.ones((3, 4, 6))}
    state = init(params)
    assert state["v"]["mat"]["vr"].shape == (8,)
    assert state["v"]["mat"]["vc"].shape == (16,)
    assert state["v"]["t3"]["vr"].shape == (3, 4)
    assert state["v"]["t3"]["vc"].shape == (3, 6)
    assert state["v"]["vec"]["v"].shape == (5,)
    # factored state is ~ (r+c) not r·c
    n_state = sum(np.prod(x.shape) for x in jax.tree.leaves(state["v"]))
    n_param = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    assert n_state < 0.5 * n_param


def test_clip_by_global_norm():
    g = {"a": jnp.ones(100) * 10}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    g2 = {"a": jnp.ones(4) * 0.01}
    same, _ = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(np.asarray(same["a"]), 0.01)


def test_schedule_warmup_and_decay():
    fn = linear_warmup_cosine(1e-3, 100, 1000)
    lrs = [float(fn(jnp.int32(s))) for s in (0, 50, 100, 500, 1000)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(5e-4)
    assert lrs[2] == pytest.approx(1e-3)
    assert lrs[3] < lrs[2] and lrs[4] < lrs[3]


# --- checkpoint -------------------------------------------------------------


def test_checkpoint_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": jnp.arange(12.0).reshape(3, 4),
                "nested": [jnp.ones(2), {"x": jnp.zeros((2, 2))}]}
        for step in (10, 20, 30, 40):
            save(d, step, tree, keep=2)
        assert latest_step(d) == 40
        # keep=2 GC'd the old ones
        steps = [int(n.split("_")[1]) for n in os.listdir(d)
                 if n.startswith("step_") and not n.endswith(".tmp")]
        assert sorted(steps) == [30, 40]
        out, step, meta = restore(d, tree)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(tree["w"]))


def test_checkpoint_ignores_partial_writes():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": jnp.ones(3)}
        save(d, 10, tree)
        # simulate a crashed writer: orphan tmp dir without manifest
        os.makedirs(os.path.join(d, "step_000000020.tmp"))
        assert latest_step(d) == 10
        out, step, _ = restore(d, tree)
        assert step == 10


def test_checkpoint_shape_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, {"w": jnp.ones((2, 2))})
        with pytest.raises(ValueError, match="shape"):
            restore(d, {"w": jnp.ones((3, 3))})


def test_elastic_reload_shard_fn_called():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": jnp.ones((4, 4))}
        save(d, 5, tree)
        calls = []

        def shard_fn(t):
            calls.append(True)
            return jax.tree.map(jnp.asarray, t)

        mgr = CheckpointManager(d)
        out, step, _ = mgr.restore_or_init(lambda: tree, shard_fn=shard_fn)
        assert step == 5 and calls


# --- data determinism -------------------------------------------------------


def test_streams_deterministic():
    from repro.data import CTRStream, GeoCorpus, GeoCorpusConfig, LMStream
    s1 = LMStream(512, seed=7).batch(3, 4, 32)
    s2 = LMStream(512, seed=7).batch(3, 4, 32)
    np.testing.assert_array_equal(s1["tokens"], s2["tokens"])
    c1 = CTRStream(13, [100] * 4, seed=7).batch(5, 16)
    c2 = CTRStream(13, [100] * 4, seed=7).batch(5, 16)
    np.testing.assert_array_equal(c1["sparse"], c2["sparse"])
    g1 = GeoCorpus(GeoCorpusConfig(n_objects=200, n_queries=40, seed=3))
    g2 = GeoCorpus(GeoCorpusConfig(n_objects=200, n_queries=40, seed=3))
    np.testing.assert_array_equal(g1.obj_doc, g2.obj_doc)
    b1 = g1.train_batch(9, 8, np.arange(40))
    b2 = g2.train_batch(9, 8, np.arange(40))
    np.testing.assert_array_equal(b1["q_tokens"], b2["q_tokens"])


def test_corpus_ground_truth_sane(small_corpus):
    c = small_corpus
    for i in range(0, c.cfg.n_queries, 10):
        pos = c.positives[i]
        assert len(pos) >= 1
        # positives share the query's topic
        assert (c.obj_topic[pos] == c.q_topic[i]).all()
    # near-distance concentration (the paper Fig. 1b pattern)
    d_pos = [np.linalg.norm(c.obj_loc[p] - c.q_loc[i], axis=1).mean()
             for i, p in enumerate(c.positives)]
    assert np.mean(d_pos) < 0.15


# --- gradient compression ---------------------------------------------------


@hypothesis.given(st.integers(0, 5))
@hypothesis.settings(max_examples=5, deadline=None)
def test_quantize_roundtrip_error_bounded(seed):
    r = np.random.default_rng(seed)
    g = jnp.asarray(r.normal(0, 1, size=(320,)), jnp.float32)
    q, s, n = comp.quantize_int8(g, block=64)
    deq = comp.dequantize_int8(q, s, n, g.shape)
    # error per element <= scale/2 = max|block|/254
    err = np.abs(np.asarray(deq - g))
    max_per_block = np.abs(np.asarray(g)).reshape(-1, 64).max(1)
    bound = np.repeat(max_per_block / 254 + 1e-6, 64)
    assert (err <= bound + 1e-6).all()


def test_error_feedback_reduces_bias():
    r = np.random.default_rng(0)
    g = jnp.asarray(r.normal(0, 1, size=(256,)), jnp.float32)
    res = jnp.zeros_like(g)
    acc_plain = jnp.zeros_like(g)
    acc_ef = jnp.zeros_like(g)
    for _ in range(50):
        q, s, n = comp.quantize_int8(g, block=64)
        acc_plain += comp.dequantize_int8(q, s, n, g.shape)
        qs, res_new = comp.compress_tree_for_allreduce(
            {"g": g}, {"g": res}, block=64)
        q2, s2 = qs["g"]
        acc_ef += comp.dequantize_int8(q2, s2, 256, g.shape)
        res = res_new["g"]
    target = np.asarray(g) * 50
    # error feedback keeps the accumulated estimate unbiased
    assert (np.abs(np.asarray(acc_ef) - target).mean()
            <= np.abs(np.asarray(acc_plain) - target).mean() + 1e-3)


def test_compressed_psum_matches_mean(rng):
    """shard_map int8 psum ≈ plain mean of per-device grads."""
    from jax.sharding import Mesh, PartitionSpec as P
    if jax.device_count() < 1:
        pytest.skip("no devices")
    g = jnp.asarray(rng.normal(size=(jax.device_count(), 128)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()), ("d",))
    out = jax.shard_map(
        lambda x: comp.compressed_psum(x[0], "d")[None],
        mesh=mesh, in_specs=P("d", None), out_specs=P("d", None))(g)
    ref = g.mean(axis=0)
    err = np.abs(np.asarray(out)[0] - np.asarray(ref))
    assert err.max() < np.abs(np.asarray(g)).max() / 100


# --- resilience -------------------------------------------------------------


def test_straggler_monitor_flags_slow_host():
    m = StragglerMonitor(patience=2)
    for step in range(5):
        for h in range(8):
            m.record(f"h{h}", 1.0 + 0.01 * h)
        m.record("h8", 9.0)          # 9× slower
        flagged = m.flagged()
    assert flagged == ["h8"]


def test_straggler_monitor_tolerates_jitter():
    m = StragglerMonitor(patience=3)
    r = np.random.default_rng(0)
    for step in range(10):
        for h in range(8):
            m.record(f"h{h}", 1.0 + 0.05 * r.random())
        assert m.flagged() == []


def test_elastic_planner():
    p = ElasticPlanner(chips_per_pod=256, tp_divisor=16, global_batch=256)
    plan2 = p.plan(2)
    assert plan2.shape == (2, 16, 16) and plan2.n_chips == 512
    plan1 = p.plan(1)
    assert plan1.shape == (16, 16) and plan1.n_chips == 256
    assert p.plan(0) is None
    # 3 pods with batch 256: 256 % 3 != 0 -> falls back to 2 pods
    assert p.plan(3).shape == (2, 16, 16)


# --- sharding rules ---------------------------------------------------------


def test_param_specs_divisibility_guard():
    from jax.sharding import PartitionSpec as P
    rules = {"dp": ("data",), "tp": ("model",),
             "_sizes": {"data": 16, "model": 16}}
    shapes = {"item_embed": jax.ShapeDtypeStruct((1000001, 64), jnp.float32),
              "tables": [jax.ShapeDtypeStruct((512, 64), jnp.float32)]}
    with sh.axis_rules(rules):
        specs = sh.param_specs(shapes, sh.REC_PARAM_RULES)
    assert specs["item_embed"] == P(None, None)     # 1000001 % 16 != 0
    assert specs["tables"][0] == P("model", None)   # 512 % 16 == 0


def test_param_specs_lm_rules():
    from jax.sharding import PartitionSpec as P
    rules = {"dp": ("pod", "data"), "tp": ("model",),
             "_sizes": {"pod": 2, "data": 16, "model": 16}}
    shapes = {
        "periods": {"attn": {"wq": {"w": jax.ShapeDtypeStruct(
            (4, 1, 2048, 4096), jnp.float32)}}},
        "embed": jax.ShapeDtypeStruct((32768, 2048), jnp.float32),
    }
    with sh.axis_rules(rules):
        specs = sh.param_specs(shapes, sh.LM_PARAM_RULES)
    assert specs["periods"]["attn"]["wq"]["w"] == P(
        None, None, ("pod", "data"), "model")
    assert specs["embed"] == P("model", ("pod", "data"))
