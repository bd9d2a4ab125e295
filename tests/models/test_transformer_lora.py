"""Transformer + LoRA/PEFT tests: forward contract, adapter semantics,
freezing, wire filtering, and the federated LoRA + FedOpt config
(reference capability: examples/bert_finetuning_example,
examples/fedllm_example, utils/peft_parameter_extraction.py:7)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fl4health_tpu.clients import engine
from fl4health_tpu.core import remat as remat_names
from fl4health_tpu.datasets.synthetic import synthetic_text_classification
from fl4health_tpu.metrics import efficient
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.transformer import LoraDense, TransformerClassifier
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedopt import FedOpt
from tests.models.remat_probe import pallas_calls
from fl4health_tpu.utils.peft import (
    lora_exchanger,
    lora_trainable_mask,
    masked_optimizer,
    peft_parameter_paths,
)

VOCAB, SEQ, CLASSES = 128, 16, 4


def small_model(**kw):
    defaults = dict(
        vocab_size=VOCAB, n_classes=CLASSES, d_model=32, n_heads=2,
        n_layers=2, d_ff=64, max_len=SEQ,
    )
    defaults.update(kw)
    return TransformerClassifier(**defaults)


class TestTransformer:
    def test_forward_shapes_and_contract(self):
        m = small_model()
        x, _ = synthetic_text_classification(jax.random.PRNGKey(0), 6, VOCAB, SEQ, CLASSES)
        variables = m.init(jax.random.PRNGKey(1), x, train=False)
        preds, feats = m.apply(variables, x, train=False)
        assert preds["prediction"].shape == (6, CLASSES)
        assert feats["features"].shape == (6, 32)

    def test_pad_positions_are_inert(self):
        """Trailing pads must not influence logits: the same tokens scored at
        full padded length and at their exact length agree (attention mask +
        masked mean-pool both screen the pads)."""
        m = small_model()
        tokens = [5, 6, 7, 8]
        x_padded = jnp.asarray([tokens + [0] * (SEQ - 4)], jnp.int32)
        x_exact = jnp.asarray([tokens], jnp.int32)
        variables = m.init(jax.random.PRNGKey(0), x_padded, train=False)
        out_padded, _ = m.apply(variables, x_padded, train=False)
        out_exact, _ = m.apply(variables, x_exact, train=False)
        np.testing.assert_allclose(
            np.asarray(out_padded["prediction"]),
            np.asarray(out_exact["prediction"]),
            atol=1e-5,
        )

    def test_bf16_compute_path(self):
        m = small_model(dtype=jnp.bfloat16)
        x, _ = synthetic_text_classification(jax.random.PRNGKey(0), 4, VOCAB, SEQ, CLASSES)
        variables = m.init(jax.random.PRNGKey(1), x, train=False)
        preds, _ = m.apply(variables, x, train=False)
        # params stay fp32 (mixed precision), logits come back fp32
        kernels = [
            p for p in jax.tree_util.tree_leaves(variables["params"]) if p.ndim == 2
        ]
        assert all(k.dtype == jnp.float32 for k in kernels)
        assert preds["prediction"].dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(preds["prediction"])))


class TestLora:
    def test_lora_b_zero_init_means_identity_at_start(self):
        """With lora_b = 0, the adapted layer equals the base layer."""
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
        base = LoraDense(6, rank=0)
        lora = LoraDense(6, rank=2)
        vb = base.init(jax.random.PRNGKey(1), x)
        vl = lora.init(jax.random.PRNGKey(1), x)
        # same base kernel init (same rng), plus lora_a/lora_b
        assert set(vl["params"]) == {"kernel", "bias", "lora_a", "lora_b"}
        assert bool(jnp.all(vl["params"]["lora_b"] == 0))
        np.testing.assert_allclose(
            np.asarray(base.apply(vb, x)), np.asarray(lora.apply(vl, x)), atol=1e-6
        )

    def test_peft_paths_and_exchanger_filter(self):
        m = small_model(lora_rank=2)
        x = jnp.zeros((1, SEQ), jnp.int32)
        params = m.init(jax.random.PRNGKey(0), x, train=False)["params"]
        paths = peft_parameter_paths(params)
        assert paths, "must find adapter params"
        assert all(
            any(mk in p.split(".") for mk in ("lora_a", "lora_b", "classifier"))
            for p in paths
        )
        # the exchanger zeroes everything else on push
        ex = lora_exchanger()
        pushed = ex.push(params)
        flat = jax.tree_util.tree_flatten_with_path(pushed)[0]
        for key_path, leaf in flat:
            dotted = ".".join(str(getattr(k, "key", k)) for k in key_path)
            is_peft = any(
                mk in dotted.split(".") for mk in ("lora_a", "lora_b", "classifier")
            )
            if not is_peft:
                assert bool(jnp.all(leaf == 0)), f"{dotted} leaked onto the wire"

    @pytest.mark.slow
    def test_masked_optimizer_freezes_base_weights(self):
        m = small_model(lora_rank=2, n_layers=1)
        x, y = synthetic_text_classification(jax.random.PRNGKey(0), 8, VOCAB, SEQ, CLASSES)
        params = m.init(jax.random.PRNGKey(1), x, train=False)["params"]
        mask = lora_trainable_mask(params)
        tx = masked_optimizer(optax.adam(1e-2), mask)
        state = tx.init(params)

        def loss_fn(p):
            preds, _ = m.apply({"params": p}, x, train=False)
            return engine.masked_cross_entropy(
                preds["prediction"], y, jnp.ones((x.shape[0],))
            )

        grads = jax.grad(loss_fn)(params)
        updates, _ = tx.update(grads, state, params)
        new_params = optax.apply_updates(params, updates)

        flat_old = jax.tree_util.tree_flatten_with_path(params)[0]
        flat_new = jax.tree_util.tree_leaves(new_params)
        moved = frozen_moved = 0
        for (key_path, old), new in zip(flat_old, flat_new):
            dotted = ".".join(str(getattr(k, "key", k)) for k in key_path)
            changed = bool(jnp.any(old != new))
            is_trainable = any(
                mk in dotted.split(".") for mk in ("lora_a", "lora_b", "classifier")
            )
            if is_trainable and changed:
                moved += 1
            if not is_trainable and changed:
                frozen_moved += 1
        assert moved > 0, "adapters must train"
        assert frozen_moved == 0, "base weights must stay frozen"


class TestFederatedLora:
    def test_fedopt_lora_round_learns_and_keeps_base_frozen(self):
        """The bert_finetuning/fedllm capability: FedOpt server optimizer +
        LoRA-only exchange, 4 clients, AG-News-shaped synthetic data."""
        m = small_model(lora_rank=4)
        model = engine.from_flax(m)
        datasets = []
        for i in range(4):
            x, y = synthetic_text_classification(
                jax.random.PRNGKey(10 + i), 48, VOCAB, SEQ, CLASSES, class_sep=3.0
            )
            datasets.append(ClientDataset(x[:32], y[:32], x[32:], y[32:]))

        sample_x = datasets[0].x_train[:1]
        init_params = model.init(jax.random.PRNGKey(0), sample_x)[0]
        mask = lora_trainable_mask(init_params)
        logic = engine.ClientLogic(model, engine.masked_cross_entropy)
        sim = FederatedSimulation(
            logic=logic,
            tx=masked_optimizer(optax.adam(1e-2), mask),
            strategy=FedOpt(optax.adam(1e-2)),
            datasets=datasets,
            batch_size=8,
            metrics=MetricManager((efficient.accuracy(),)),
            local_steps=8,
            seed=3,
            exchanger=lora_exchanger(),
        )
        base_before = jax.device_get(
            sim.client_states.params["layer_0"]["attn"]["q_proj"]["kernel"]
        )
        history = sim.fit(5)
        base_after = jax.device_get(
            sim.client_states.params["layer_0"]["attn"]["q_proj"]["kernel"]
        )
        np.testing.assert_allclose(base_before, base_after, atol=1e-7)
        assert history[-1].fit_losses["backward"] < history[0].fit_losses["backward"]
        assert history[-1].eval_metrics["accuracy"] > 0.3  # 0.25 = chance


class TestRemat:
    @pytest.mark.slow
    def test_remat_gradients_match_unremat(self):
        # remat=True must be a pure memory/FLOPs trade: same params tree,
        # same gradients (jax.checkpoint recomputes, never changes math)
        from jax.flatten_util import ravel_pytree

        a, b = small_model(), small_model(remat=True)
        x, _ = synthetic_text_classification(
            jax.random.PRNGKey(2), 4, VOCAB, SEQ, CLASSES
        )
        v = a.init(jax.random.PRNGKey(3), x, train=False)

        def sq(model):
            return jax.grad(lambda p: jnp.sum(jnp.square(
                model.apply(p, x, train=False)[0]["prediction"])))(v)

        fa = ravel_pytree(sq(a))[0]
        fb = ravel_pytree(sq(b))[0]
        np.testing.assert_allclose(np.asarray(fa), np.asarray(fb),
                                   atol=1e-5, rtol=1e-5)

    @staticmethod
    def _flash_model(**kw):
        import functools

        return small_model(attention_fn=functools.partial(
            flash_attention, block_q=8, block_k=8), **kw)

    @pytest.mark.parametrize("site", ["kept", "bare"])
    def test_a_rematerialised_block_runs_its_flash_forward_once(
            self, site, monkeypatch):
        """Two blocks: two ``flash_fwd`` in the gradient with the kernel's
        results kept (the site's policy), four under a bare ``nn.remat``
        (what the site was); the gradients are those without remat."""
        from jax.flatten_util import ravel_pytree

        if site == "bare":
            monkeypatch.setattr(remat_names, "keep", lambda names: None)
        a, b = self._flash_model(), self._flash_model(remat=True)
        x, _ = synthetic_text_classification(
            jax.random.PRNGKey(2), 4, VOCAB, SEQ, CLASSES
        )
        v = a.init(jax.random.PRNGKey(3), x, train=False)

        def sq(model):
            return jax.grad(lambda p: jnp.sum(jnp.square(
                model.apply(p, x, train=False)[0]["prediction"])))

        calls = {name: pallas_calls(jax.make_jaxpr(sq(b))(v).jaxpr, name)
                 for name in ("flash_fwd", "flash_dq", "flash_dkv")}
        assert calls == {"flash_fwd": 2 if site == "kept" else 4,
                         "flash_dq": 2, "flash_dkv": 2}
        assert pallas_calls(jax.make_jaxpr(sq(a))(v).jaxpr, "flash_fwd") == 2
        np.testing.assert_allclose(
            np.asarray(ravel_pytree(sq(a)(v))[0]),
            np.asarray(ravel_pytree(sq(b)(v))[0]), atol=1e-5, rtol=1e-5)

    def test_build_gauges_say_what_a_block_keeps(self):
        """``out`` [B*H, Tp, 64] (a head of 16 padded to 64 lanes: the
        transposed path, one call a block) and the statistic [B*H, Tp], one
        of each a block, over the clients."""
        b, heads, clients = 3, 2, 4
        want = clients * (b * heads * SEQ * 64 * 4 + b * heads * SEQ * 4)
        transposed = {"flash_calls_lane_indexed": 0,
                      "flash_calls_transposed": 2}
        gauges = engine.from_flax(self._flash_model(remat=True)).build_gauges(
            (b, SEQ), clients)
        assert gauges == {**transposed, "remat_saved_names": 2,
                          "remat_saved_bytes_per_layer": want}
        bf16 = self._flash_model(remat=True, dtype=jnp.bfloat16)
        assert bf16.build_gauges((b, SEQ), clients)[
            "remat_saved_bytes_per_layer"] == clients * (
                b * heads * SEQ * 64 * 2 + b * heads * SEQ * 4)
        nothing = {"remat_saved_names": 0, "remat_saved_bytes_per_layer": 0}
        # no remat: no site; the dense core names nothing and calls nothing
        assert self._flash_model().build_gauges((b, SEQ), clients) == {
            **transposed, **nothing}
        assert small_model(remat=True).build_gauges((b, SEQ), clients) == {
            "flash_calls_lane_indexed": 0, "flash_calls_transposed": 0,
            **nothing}

    def test_at_the_flash_cells_shape_no_call_is_transposed(self):
        """BERT-base widths under the long-document job (4 clients x batch 8
        x 2,048 tokens, 12 heads of 64, bf16, remat, blocks 128/128): every
        block's call addresses q / k / v in the projections' [B, T, 768],
        two heads a lane block, and a block keeps ``out`` so, not padded or
        transposed, with the statistic [B, 12, Tp]."""
        import functools

        module = TransformerClassifier(
            vocab_size=30522, n_classes=4, d_model=768, n_heads=12,
            n_layers=12, d_ff=3072, max_len=2048, dtype=jnp.bfloat16,
            remat=True, attention_fn=functools.partial(
                flash_attention, block_q=128, block_k=128))
        assert module.build_gauges((8, 2048), 4) == {
            "flash_calls_lane_indexed": 12, "flash_calls_transposed": 0,
            "remat_saved_names": 2,
            "remat_saved_bytes_per_layer": 4 * (8 * 2048 * 768 * 2
                                                + 8 * 12 * 2048 * 4)}
