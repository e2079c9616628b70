"""Model-substrate tests: per-family forward/train correctness, decode ==
full-forward equivalence, early-exit semantics, and abstract init."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (
    EarlyExitResNet,
    LMConfig,
    ResNetConfig,
    build_model,
    split_params,
)
from repro.models.encdec import EncDecLM


def tiny_cfg(family="dense", **kw):
    base = dict(
        arch_id=f"tiny-{family}", family=family, num_layers=4, d_model=32,
        num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=61, exits=(2, 4),
    )
    if family == "moe":
        base.update(num_experts=4, top_k=2, num_shared_experts=1,
                    d_ff_expert=16, dense_prefix=1, moe_group_size=8,
                    moe_capacity_factor=100.0)
    if family == "jamba":
        base.update(num_layers=8, exits=(4, 8), attn_period=4, attn_offset=3,
                    moe_period=2, num_experts=4, top_k=2, d_ff_expert=16,
                    moe_group_size=8, moe_capacity_factor=100.0,
                    mamba_d_state=8, mamba_d_conv=3)
    if family == "rwkv":
        base.update(num_kv_heads=4)
    if family == "encdec":
        base.update(num_kv_heads=4, num_encoder_layers=2, frontend="audio",
                    frontend_seq=5)
    base.update(kw)
    return LMConfig(**base)


def make_batch(cfg, key=0, batch=2, seq=6):
    ks = jax.random.split(jax.random.key(key), 3)
    toks = jax.random.randint(ks[0], (batch, seq), 0, cfg.vocab_size)
    b = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        b["src_embeds"] = jax.random.normal(
            ks[1], (batch, cfg.frontend_seq, cfg.d_model))
    return b


FAMILIES = ["dense", "moe", "rwkv", "jamba", "encdec"]


@pytest.mark.parametrize("family", FAMILIES)
class TestFamilies:
    def test_train_loss_finite_and_grads(self, family):
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        batch = make_batch(cfg)
        loss, metrics = model.train_loss(values, batch)
        assert jnp.isfinite(loss)
        assert "nll_final" in metrics
        g = jax.grad(lambda v: model.train_loss(v, batch)[0])(values)
        norms = [float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g)]
        assert all(np.isfinite(n) for n in norms)
        assert sum(norms) > 0

    def test_forward_exit_shapes(self, family):
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        batch = make_batch(cfg)
        for e in range(cfg.num_exits):
            logits = model.forward_exit(values, batch, e)
            assert logits.shape == (2, 6, cfg.vocab_size)
            assert bool(jnp.all(jnp.isfinite(logits)))

    def test_decode_matches_full_forward(self, family):
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(1)))
        batch = make_batch(cfg, key=2)
        toks = batch["tokens"]
        e = cfg.num_exits - 1
        full = model.forward_exit(values, batch, e)
        if family == "encdec":
            cache = model.prepare_decode_cache(
                values, batch["src_embeds"], 2, 10, e)
        else:
            cache = model.init_cache(2, 10, e)
        outs = []
        for i in range(toks.shape[1]):
            lg, cache = model.decode_step(values, toks[:, i:i + 1], cache, e)
            outs.append(lg[:, 0])
        step = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(np.asarray(full), np.asarray(step),
                                   rtol=5e-3, atol=5e-3)

    def test_early_exit_cheaper_than_final(self, family):
        # Early exits must execute strictly fewer layers: check by FLOP count
        # of the jitted computation.
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        batch = make_batch(cfg)

        def flops(e):
            c = jax.jit(
                lambda v, b: model.forward_exit(v, b, e)
            ).lower(values, batch).compile()
            ca = c.cost_analysis()
            return ca.get("flops", 0.0)

        assert flops(0) < flops(cfg.num_exits - 1)

    def test_abstract_init_no_alloc(self, family):
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        shapes, axes = model.abstract(jax.random.key(0))
        leaves = jax.tree.leaves(shapes)
        assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        axes_leaves = jax.tree.leaves(
            axes, is_leaf=lambda x: isinstance(x, tuple))
        # every param has an axes tuple matching its rank
        flat_shapes = jax.tree.leaves(shapes)
        for s, a in zip(flat_shapes, axes_leaves):
            assert len(a) == len(s.shape), (s.shape, a)

    def test_prefill_logits_match_forward_last_position(self, family):
        cfg = tiny_cfg(family)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(3)))
        batch = make_batch(cfg, key=4)
        e = 0
        full = model.forward_exit(values, batch, e)
        pre, _ = model.prefill(values, batch, e)
        np.testing.assert_allclose(
            np.asarray(full[:, -1:, :]), np.asarray(pre), rtol=5e-3, atol=5e-3)


class TestMoESpecifics:
    def test_capacity_drops_bounded(self):
        # With capacity factor 1.0 and adversarially identical tokens, drops
        # happen but output stays finite and bounded.
        cfg = tiny_cfg("moe", moe_capacity_factor=1.0)
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        toks = jnp.zeros((2, 6), jnp.int32)  # all tokens identical
        logits = model.forward_exit(values, {"tokens": toks}, 1)
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_router_types(self):
        for router in ("softmax", "sigmoid"):
            cfg = tiny_cfg("moe", moe_router=router)
            model = build_model(cfg)
            values, _ = split_params(model.init(jax.random.key(0)))
            loss, _ = model.train_loss(values, make_batch(cfg))
            assert jnp.isfinite(loss)

    def test_moe_aux_loss_positive(self):
        cfg = tiny_cfg("moe")
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        _, metrics = model.train_loss(values, make_batch(cfg))
        assert float(metrics["moe_aux"]) > 0


class TestRWKVSpecifics:
    def test_state_is_o1_in_sequence(self):
        cfg = tiny_cfg("rwkv")
        model = build_model(cfg)
        c_small = model.init_cache(2, 10, 1)
        c_large = model.init_cache(2, 100000, 1)
        sz = lambda c: sum(np.prod(x.shape) for x in jax.tree.leaves(c))
        assert sz(c_small) == sz(c_large)  # no KV growth: attention-free

    def test_decay_in_unit_interval(self):
        from repro.models.rwkv6 import RWKV6Config, init_time_mix
        from repro.models.common import split_params as sp
        cfg = RWKV6Config(d_model=16, num_heads=2, d_ff=32)
        params, _ = sp(init_time_mix(jax.random.key(0), cfg))
        x = jax.random.normal(jax.random.key(1), (1, 4, 16))
        logit = params["decay_base"] + jnp.tanh(
            x @ params["decay_a"]) @ params["decay_b"]
        w = jnp.exp(-jnp.exp(logit))
        assert bool(jnp.all((w > 0) & (w < 1)))


class TestJambaSpecifics:
    def test_exit_alignment_enforced(self):
        with pytest.raises(AssertionError):
            build_model(tiny_cfg("jamba", exits=(3, 8)))

    def test_kv_cache_only_for_attn_sublayers(self):
        cfg = tiny_cfg("jamba")
        model = build_model(cfg)
        cache = model.init_cache(2, 10, 1)
        seg = cache["segments"][0]
        kinds = model._sub_kinds()
        for j, (mixer, _) in enumerate(kinds):
            if mixer == "attn":
                assert "k" in seg[f"sub{j}"]
            else:
                assert "h" in seg[f"sub{j}"]  # mamba state


class TestEncDecSpecifics:
    def test_exits_are_decoder_only(self):
        # encoder always runs fully: exit 0 and exit 1 share encoder cost;
        # difference in FLOPs comes from decoder segments only.
        cfg = tiny_cfg("encdec")
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        batch = make_batch(cfg)
        enc = model.encode(values, batch["src_embeds"])
        assert enc.shape == (2, cfg.frontend_seq, cfg.d_model)


class TestResNet:
    def test_paper_variants_structure(self):
        from repro.models.resnet import STAGE_BLOCKS
        assert STAGE_BLOCKS["resnet50"] == (3, 4, 6, 3)
        assert STAGE_BLOCKS["resnet101"] == (3, 4, 23, 3)
        assert STAGE_BLOCKS["resnet152"] == (3, 8, 36, 3)

    def test_reduced_train_and_exits(self):
        cfg = ResNetConfig(variant="resnet50", num_classes=10,
                           width_multiplier=0.125, blocks_override=(1, 1, 1, 1))
        model = EarlyExitResNet(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        imgs = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
        lbls = jax.random.randint(jax.random.key(2), (4,), 0, 10)
        loss, metrics = model.train_loss(values, {"images": imgs,
                                                  "labels": lbls})
        assert jnp.isfinite(loss)
        for e in range(4):
            lg = model.forward_exit(values, imgs, e)
            assert lg.shape == (4, 10)

    def test_exit_flops_ordering(self):
        cfg = ResNetConfig(variant="resnet50", num_classes=10,
                           width_multiplier=0.25, blocks_override=(1, 1, 1, 1))
        model = EarlyExitResNet(cfg)
        values, _ = split_params(model.init(jax.random.key(0)))
        imgs = jnp.zeros((2, 32, 32, 3))

        def flops(e):
            ca = jax.jit(
                lambda v, x: model.forward_exit(v, x, e)
            ).lower(values, imgs).compile().cost_analysis()
            return ca.get("flops", 0.0)

        f = [flops(e) for e in range(4)]
        assert f[0] < f[1] < f[2] < f[3]
