"""Token denoising by blocks as the program takes it (``objectives/
class_label.py`` has the contract): a ``ClientLogic`` whose training and
evaluation loss is ``reference/objectives/toy_block_denoise.loss`` over the
rows the engine calls valid, and the clients' metric, the mean probability
the model gives its best token at the masked positions."""

from __future__ import annotations


def build_logic(model_def, cfg: dict, job: dict):
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.clients import engine

    def weighted_token_loss(logits, batch):
        clean = batch.x[:, 1]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
        real = ((clean > 0) * batch.example_mask[:, None]).astype(jnp.float32)
        return jnp.sum(batch.y * nll * real) / jnp.maximum(jnp.sum(real), 1.0)

    class BlockDenoiseLogic(engine.ClientLogic):
        def training_loss(self, preds, features, batch, params, state, ctx):
            return weighted_token_loss(preds["prediction"], batch), {}

        eval_loss = training_loss

    # no criterion: one over (predictions, targets, row mask) cannot see the
    # clean ids, which are a row of the batch's x
    return BlockDenoiseLogic(model_def, None)


def build_metrics(cfg: dict, job: dict):
    import jax
    import jax.numpy as jnp

    from fl4health_tpu.metrics.base import Metric, MetricManager

    def update(state, logits, weights, mask):
        best = jnp.max(jax.nn.softmax(logits.astype(jnp.float32), -1), -1)
        at = (weights > 0) * mask[:, None]
        return {"sum": state["sum"] + jnp.sum(best * at),
                "n": state["n"] + jnp.sum(at)}

    return MetricManager((Metric(
        "masked_top1_prob",
        init=lambda: {"sum": jnp.zeros(()), "n": jnp.zeros(())},
        update=update,
        compute=lambda s: s["sum"] / jnp.maximum(s["n"], 1.0)),))
