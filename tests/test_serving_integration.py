"""End-to-end integration: live serving engine with real jitted models on
CPU, measured profiles, and the EdgeServing scheduler; plus a short real
training run (loss must decrease)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (
    EdgeServingScheduler,
    Request,
    SchedulerConfig,
    make_scheduler,
)
from repro.models import build_model, split_params
from repro.optim import AdamW
from repro.runtime.server import ServedModel, ServingEngine, measure_profile
from repro.runtime.trainer import make_train_step


def _tiny_lm(arch: str, key: int, num_layers=2, d=32, vocab=64):
    from repro.models.transformer import LMConfig
    cfg = LMConfig(
        arch_id=f"{arch}-{key}", family="dense", num_layers=num_layers,
        d_model=d, num_heads=4, num_kv_heads=2, d_ff=2 * d,
        vocab_size=vocab, exits=tuple(range(1, num_layers + 1)),
    )
    model = build_model(cfg)
    values, _ = split_params(model.init(jax.random.key(key)))
    return cfg, model, values


def _served(cfg, model, values, name, seq=8):
    def forward(v, x, e):
        return model.forward_exit(v, {"tokens": x}, e)

    def data(b):
        return jnp.zeros((b, seq), jnp.int32)

    return ServedModel(name=name, values=values, forward_fn=forward,
                       data_fn=data, num_exits=cfg.num_exits)


@pytest.fixture(scope="module")
def deployment():
    # three models of increasing cost, all with 2 exit points (the paper's
    # R50 < R101 < R152 pattern)
    models = []
    for i, d in enumerate((16, 32, 64)):
        cfg, model, values = _tiny_lm(f"m{i}", i, num_layers=2, d=d)
        models.append(_served(cfg, model, values, f"model{i}"))
    return models


class TestLiveServing:
    def test_measured_profile_is_sane(self, deployment):
        # the median of many repeats: a p95 of 3 is the worst of 3, which
        # one stall of a loaded host decides
        table = measure_profile(deployment, batch_sizes=[1, 2, 4],
                                repeats=15, warmup=1, percentile=50.0)
        assert table.latency.shape == (3, 2, 3)
        assert np.all(table.latency > 0)
        # deeper exits of the deepest model cost >= its shallowest exit
        assert np.all(table.latency[2, -1, :] >= table.latency[2, 0, :] * 0.5)

    def test_engine_serves_all_requests(self, deployment):
        table = measure_profile(deployment, batch_sizes=[1, 2, 4],
                                repeats=2, warmup=1)
        cfg = SchedulerConfig(slo=10.0, max_batch=4)  # generous SLO on CPU
        sched = EdgeServingScheduler(table, cfg)
        engine = ServingEngine(deployment, sched)
        engine.warmup([1, 2, 4])
        arrivals = [
            Request(req_id=i, model=i % 3, arrival=i * 0.002)
            for i in range(30)
        ]
        completions, span = engine.run(arrivals, duration=0.06, drain=True)
        assert len(completions) == 30
        m = engine.metrics(table, slo=10.0, span=span)
        assert m.violation_ratio == 0.0
        ids = sorted(c.req_id for c in completions)
        assert ids == list(range(30))

    def test_engine_respects_time_division(self, deployment):
        table = measure_profile(deployment, batch_sizes=[1, 2],
                                repeats=2, warmup=1)
        sched = make_scheduler("all-final", table,
                               SchedulerConfig(slo=10.0, max_batch=2))
        engine = ServingEngine(deployment, sched)
        engine.warmup([1, 2])
        arrivals = [Request(req_id=i, model=0, arrival=0.0) for i in range(6)]
        completions, _ = engine.run(arrivals, duration=0.01, drain=True)
        # quanta are serial: completion intervals must not overlap
        spans = sorted((c.dispatch, c.finish) for c in completions)
        for (a1, b1), (a2, b2) in zip(spans, spans[1:]):
            if a1 != a2:  # different quanta
                assert a2 >= b1 - 1e-9


class TestEngineDrainCap:
    """Regression: ``drain=True`` busy-waited forever when a policy left
    queues non-empty while ``decide`` kept returning ``None`` past
    ``duration`` (the simulator has ``drain_cap``; the live engine had no
    equivalent). The engine now mirrors the simulator's cap and surfaces
    stranded requests via ``residual_queue``."""

    def _never_scheduler(self):
        from repro.core import ProfileTable, Scheduler

        class NeverScheduler(Scheduler):
            name = "never-stub"

            def decide(self, snapshot):
                return None  # e.g. a pruning baseline that stops dispatching

        return NeverScheduler(ProfileTable.paper_rtx3080(),
                              SchedulerConfig(slo=0.05))

    def test_drain_cap_bounds_the_busy_wait(self, deployment):
        ticks = iter(np.arange(0.0, 60.0, 0.05))
        engine = ServingEngine(deployment, self._never_scheduler(),
                               clock=lambda: float(next(ticks)))
        arrivals = [Request(req_id=i, model=0, arrival=0.0) for i in range(4)]
        completions, span = engine.run(
            arrivals, duration=0.1, drain=True, idle_sleep=0.0, drain_cap=0.5)
        assert completions == []
        assert span <= 1.0  # returned at the cap, not the clock's horizon
        m = engine.metrics(engine.scheduler.table, slo=0.05, span=span)
        assert m.residual_queue == 4

    def test_unsubmitted_tail_counts_as_residual(self, deployment):
        # An arrival beyond the cap is never ingested but must not vanish:
        # completions + dropped + residual == arrivals (simulator parity).
        ticks = iter(np.arange(0.0, 60.0, 0.05))
        engine = ServingEngine(deployment, self._never_scheduler(),
                               clock=lambda: float(next(ticks)))
        arrivals = [Request(req_id=0, model=0, arrival=0.0),
                    Request(req_id=1, model=0, arrival=30.0)]
        completions, span = engine.run(
            arrivals, duration=0.1, drain=True, idle_sleep=0.0, drain_cap=0.5)
        assert completions == []
        m = engine.metrics(engine.scheduler.table, slo=0.05, span=span)
        assert m.residual_queue == 2  # 1 queued + 1 never-ingested

    def test_default_cap_preserves_normal_drain(self, deployment):
        # sanity: a working scheduler under the default cap still drains
        table = measure_profile(deployment, batch_sizes=[1, 2],
                                repeats=2, warmup=1)
        sched = EdgeServingScheduler(table,
                                     SchedulerConfig(slo=10.0, max_batch=2))
        engine = ServingEngine(deployment, sched)
        engine.warmup([1, 2])
        arrivals = [Request(req_id=i, model=0, arrival=0.0) for i in range(4)]
        completions, _ = engine.run(arrivals, duration=0.01, drain=True)
        assert len(completions) == 4


class TestResNetTrioDeployment:
    """The paper's trio (SMOKE widths) through measure_profile ->
    EdgeServingScheduler -> ServingEngine.warmup -> ServingEngine.run."""

    BATCHES = (1, 2)

    @pytest.fixture(scope="class")
    def served(self):
        from repro.configs.edgeserving_resnets import SMOKE
        from repro.core import poisson_arrivals
        from repro.runtime.compilation import CompileCounter
        from repro.runtime.resnets import served_resnets

        models = served_resnets(SMOKE, seed=0)
        table = measure_profile(models, batch_sizes=self.BATCHES,
                                repeats=2, warmup=1)
        profiled = [dict(m.executables) for m in models]
        cfg = SchedulerConfig(slo=10.0, max_batch=max(self.BATCHES))
        engine = ServingEngine(models, EdgeServingScheduler(table, cfg))
        with CompileCounter() as warm:
            engine.warmup()
        arrivals = poisson_arrivals([60.0, 40.0, 20.0], 0.2, seed=1)
        with CompileCounter() as window:
            _, span = engine.run(arrivals, duration=0.2, drain=True)
        return dict(models=models, table=table, profiled=profiled,
                    engine=engine, arrivals=arrivals, span=span,
                    warm=warm.count, window=window.count)

    def test_served_counts_add_up(self, served):
        m = served["engine"].metrics(served["table"], slo=10.0,
                                     span=served["span"])
        assert m.num_completed > 0
        assert (m.num_completed + m.dropped + m.residual_queue
                == len(served["arrivals"]))

    def test_no_executable_created_while_serving(self, served):
        assert served["window"] == 0

    def test_each_cell_compiled_once(self, served):
        # measure_profile built every (m, e, B); warmup() reused them all
        for mod, before in zip(served["models"], served["profiled"]):
            assert set(before) == {(e, b) for e in range(mod.num_exits)
                                   for b in self.BATCHES}
            assert mod.executables == before
        assert served["warm"] == 0

    def test_inputs_are_seeded_cifar_batches(self, served):
        from repro.configs.edgeserving_resnets import SMOKE
        from repro.runtime.resnets import served_resnets

        mod = served["models"][0]
        x = mod.data_fn(2)
        assert x.shape == (2, 32, 32, 3) and x.dtype == jnp.float32
        assert float(jnp.std(x)) > 0.5
        assert mod.data_fn(2) is x          # made once, kept on the device
        again = served_resnets({"resnet50": SMOKE["resnet50"]}, seed=0)[0]
        np.testing.assert_array_equal(np.asarray(again.data_fn(2)),
                                      np.asarray(x))


class TestTrainingIntegration:
    def test_loss_decreases_tiny_lm(self):
        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        opt = AdamW(lr=5e-3, weight_decay=0.0)
        opt_state = opt.init(values)
        step = jax.jit(make_train_step(model, opt))
        key = jax.random.key(1)
        # fixed tiny corpus: the model must memorise it
        toks = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        losses = []
        for i in range(30):
            values, opt_state, metrics = step(values, opt_state, batch, i)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.7, losses[::10]
        assert np.isfinite(losses).all()

    def test_grad_accum_matches_full_batch(self):
        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        opt = AdamW(lr=1e-3, weight_decay=0.0)
        toks = jax.random.randint(jax.random.key(2), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}

        s1 = jax.jit(make_train_step(model, opt))
        s2 = jax.jit(make_train_step(model, opt, grad_accum=4))
        v1, _, m1 = s1(values, opt.init(values), batch, 0)
        v2, _, m2 = s2(values, opt.init(values), batch, 0)
        # same global batch semantics -> same loss and nearly same update
        assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                                  rel=1e-5)
        diff = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(v1), jax.tree.leaves(v2))
        )
        # Adam's rsqrt amplifies fp32 summation-order noise; 1e-3 of the
        # lr-scale update is well below one optimizer step of drift.
        assert diff < 1e-3

    def test_train_step_with_resnet(self):
        from repro.configs import resnet_configs
        from repro.models import EarlyExitResNet
        cfg = resnet_configs(smoke=True)["resnet50"]
        model = EarlyExitResNet(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        opt = AdamW(lr=1e-3, weight_decay=0.0)
        opt_state = opt.init(values)
        imgs = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
        lbls = jax.random.randint(jax.random.key(2), (8,), 0, 100)
        batch = {"images": imgs, "labels": lbls}
        step = jax.jit(make_train_step(model, opt))
        losses = []
        for i in range(10):
            values, opt_state, metrics = step(values, opt_state, batch, i)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
