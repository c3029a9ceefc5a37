"""Substrate tests: optimizers, data pipeline, checkpointing, sharding rules,
fault-tolerance primitives, elastic planning."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is optional: fall back to deterministic seeded cases
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hyp import given, settings, st

from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.data.pipeline import DataConfig, MemmapCorpus, SyntheticStream
from repro.checkpoint import store
from repro.optim import adamw, adafactor, clip_by_global_norm, global_norm, warmup_cosine
from repro.optim.adamw import apply_updates
from repro.parallel import sharding
from repro.runtime.elastic import plan_rescale
from repro.runtime.fault_tolerance import (
    FTConfig,
    FaultInjector,
    HeartbeatMonitor,
    RestartPolicy,
    StragglerDetector,
)

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------- optim

def test_adamw_converges_quadratic():
    opt = adamw(lr=0.1, weight_decay=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        up, state = opt.update(g, state, params)
        params = apply_updates(params, up)
    assert float(jnp.abs(params["x"]).max()) < 1e-2


def test_adafactor_state_is_factored():
    opt = adafactor()
    params = {"w": jnp.ones((64, 32)), "b": jnp.ones((7,))}
    st_ = opt.init(params)
    assert st_["s"]["w"]["vr"].shape == (64,)
    assert st_["s"]["w"]["vc"].shape == (32,)
    assert st_["s"]["b"]["v"].shape == (7,)
    g = jax.tree.map(jnp.ones_like, params)
    up, st2 = opt.update(g, st_, params)
    assert jax.tree.all(jax.tree.map(lambda u, p: u.shape == p.shape, up, params))


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(np.sqrt(90.0), rel=1e-5)


def test_warmup_cosine_shape():
    fn = warmup_cosine(1e-3, 10, 100)
    assert float(fn(jnp.asarray(0))) == 0.0
    assert float(fn(jnp.asarray(10))) == pytest.approx(1e-3, rel=1e-3)
    assert float(fn(jnp.asarray(100))) == pytest.approx(1e-4, rel=1e-2)


# ------------------------------------------------------------------ data

def test_synthetic_stream_deterministic_and_resumable():
    s = SyntheticStream(DataConfig(vocab=100, seq_len=16, global_batch=4, seed=1))
    a, b = s.next_batch(5), s.next_batch(5)
    assert np.array_equal(a["tokens"], b["tokens"])
    c = s.next_batch(6)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # host sharding partitions the global batch
    h0 = s.host_batch(5, 0, 2)["tokens"]
    h1 = s.host_batch(5, 1, 2)["tokens"]
    assert np.array_equal(np.concatenate([h0, h1]), a["tokens"])


def test_memmap_corpus():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tokens.bin")
        np.arange(10000, dtype=np.int32).tofile(path)
        c = MemmapCorpus(path, DataConfig(vocab=50000, seq_len=8, global_batch=2))
        b = c.next_batch(0)
        assert b["tokens"].shape == (2, 8)
        # labels are next-token shifted
        assert int(b["labels"][0, 0]) == int(b["tokens"][0, 1])


# ------------------------------------------------------------- checkpoint

def test_checkpoint_atomic_roundtrip_and_gc():
    tree = {"p": {"w": jnp.arange(12.0).reshape(3, 4)}, "step": jnp.asarray(3)}
    with tempfile.TemporaryDirectory() as d:
        for s in (1, 2, 3, 4):
            store.save(tree, d, s)
        assert store.latest_step(d) == 4
        # gc keeps 3
        names = sorted(os.listdir(d))
        assert len([n for n in names if n.startswith("step_")]) == 3
        back = store.restore(d, 4, jax.tree.map(jnp.zeros_like, tree))
        assert np.array_equal(back["p"]["w"], tree["p"]["w"])


def test_async_checkpointer():
    tree = {"w": jnp.ones((8, 8))}
    with tempfile.TemporaryDirectory() as d:
        ck = store.AsyncCheckpointer(d)
        ck.save(tree, 1)
        ck.wait()
        assert store.latest_step(d) == 1


def test_checkpoint_restores_subtree_and_defaults():
    with tempfile.TemporaryDirectory() as d:
        store.save({"a": jnp.ones((2,)), "b": jnp.zeros((3,))}, d, 1)
        like = {"a": jnp.zeros((2,)), "c": jnp.full((4,), 7.0)}  # c not in ckpt
        back = store.restore(d, 1, like)
        assert np.array_equal(back["a"], np.ones((2,)))
        assert np.array_equal(back["c"], np.full((4,), 7.0))


# -------------------------------------------------------------- sharding

MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_param_specs_tp_rules():
    cfg = get_smoke_config("stablelm_3b")
    import dataclasses
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), KEY)
    specs = sharding.param_specs(shapes, cfg, mesh=MESH)
    assert tuple(specs["units"][0]["attn"]["wq"]) == (None, None, "model")
    assert tuple(specs["units"][0]["attn"]["wo"]) == (None, "model", None)
    assert tuple(specs["units"][0]["mlp"]["w_up"]) == (None, None, "model")
    assert all(a is None for a in tuple(specs["final_norm"]))


def test_param_specs_fsdp_adds_data_axis():
    cfg = get_smoke_config("grok_1_314b")
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), KEY)
    specs = sharding.param_specs(shapes, cfg, fsdp=True, mesh=None)
    assert tuple(specs["units"][0]["moe"]["w_up"]) == (None, None, "data", "model")


def test_sanitize_drops_nondividing_axes():
    # mamba vocab 50280 % 16 != 0 → model axis must be dropped
    spec = sharding.sanitize_spec(P("model", None), (50280, 1536), MESH)
    assert tuple(spec) == (None, None)
    spec = sharding.sanitize_spec(P("model", None), (50304, 1536), MESH)
    assert tuple(spec) == ("model", None)
    # tuple axes: batch 8 not divisible by pod*data=32 → dropped
    spec = sharding.sanitize_spec(P(("pod", "data"), None), (8, 4), MESH)
    assert tuple(spec) == (None, None)


def test_filter_spec_removes_missing_axes():
    single = AbstractMesh((16, 16), ("data", "model"))
    f = sharding.filter_spec(P(("pod", "data"), "model"), single)
    assert tuple(f) == ("data", "model")


def test_batch_and_cache_specs():
    cfg = get_smoke_config("gemma2_27b")
    from repro.models import transformer as T

    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    bs = sharding.batch_specs(batch, mesh=MESH)
    assert tuple(bs["tokens"]) == (("pod", "data"), None)
    caches = jax.eval_shape(lambda: T.init_caches(cfg, 128, 512))
    cs = sharding.cache_specs(caches, cfg, mesh=MESH)
    k_spec = tuple(cs["units"][0]["k"])
    assert k_spec[1] == ("pod", "data")


# ----------------------------------------------------------------- FT

def test_straggler_detector_flags_slow_host():
    det = StragglerDetector(FTConfig(straggler_factor=2.0, straggler_patience=2))
    for t in range(20):
        det.report("h0", 1.0)
        det.report("h1", 1.0)
    assert det.report("h2", 5.0) is False  # patience 1
    assert det.report("h2", 5.0) is True  # patience 2 → confirmed


def test_heartbeat_monitor():
    hb = HeartbeatMonitor(["a", "b"], timeout_s=10.0)
    hb.beat("a", now=100.0)
    hb.beat("b", now=100.0)
    assert hb.dead_hosts(now=105.0) == []
    assert hb.dead_hosts(now=111.0) == ["a", "b"]
    hb.beat("a", now=112.0)
    assert hb.dead_hosts(now=115.0) == ["b"]


def test_restart_policy_budget():
    pol = RestartPolicy(max_restarts=2, backoff_s=0.5)
    assert pol.on_failure(RuntimeError("x")) == 0.5
    assert pol.on_failure(RuntimeError("x")) == 1.0
    with pytest.raises(RuntimeError, match="budget exhausted"):
        pol.on_failure(RuntimeError("x"))


def test_fault_injector_fires_once():
    inj = FaultInjector({3})
    inj.maybe_fail(2)
    with pytest.raises(RuntimeError):
        inj.maybe_fail(3)
    inj.maybe_fail(3)  # second pass: already fired


def test_elastic_rescale_plan():
    plan = plan_rescale({"pod": 2, "data": 16, "model": 16},
                        {"data": 16, "model": 16}, global_batch=256)
    assert plan.per_device_batch_old == 8.0
    assert plan.per_device_batch_new == 16.0
    assert any("scale-down" in n for n in plan.notes)
