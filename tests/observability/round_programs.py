"""Toy simulations of the benchmark cells' jobs and the text of the round
programs they build: shared by the tests of the program's names in those
programs (test_stage_attribution.py, test_layer_scopes.py)."""

import contextlib
import functools

import numpy as np

import jax
import jax.numpy as jnp
import optax

from fl4health_tpu.clients import engine
from fl4health_tpu.datasets.synthetic import synthetic_classification
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.metrics import efficient
from fl4health_tpu.metrics.base import MetricManager
from fl4health_tpu.models.afmoe import AfmoeClassifier
from fl4health_tpu.models.cnn import Mlp
from fl4health_tpu.models.deepseek import DeepseekV2Classifier
from fl4health_tpu.models.jamba import JambaClassifier
from fl4health_tpu.models.nemotron_h import NemotronHClassifier
from fl4health_tpu.models.transformer import TransformerClassifier
from fl4health_tpu.observability import (
    MetricsRegistry,
    Observability,
    Tracer,
)
from fl4health_tpu.observability.introspect import abstractify
from fl4health_tpu.server.simulation import (EXEC_PIPELINED, ClientDataset,
                                             FederatedSimulation)
from fl4health_tpu.strategies.fedavg import FedAvg

N_CLASSES = 3


def obs():
    return Observability(enabled=True, tracer=Tracer(),
                         registry=MetricsRegistry())


def mlp_sim(n=3, mode="auto", **kwargs):
    datasets = []
    for i in range(n):
        x, y = synthetic_classification(
            jax.random.PRNGKey(i), 40, (6,), N_CLASSES
        )
        datasets.append(ClientDataset(x[:32], y[:32], x[32:], y[32:]))
    args = dict(
        logic=engine.ClientLogic(
            engine.from_flax(Mlp(features=(12,), n_outputs=N_CLASSES)),
            engine.masked_cross_entropy,
        ),
        tx=optax.sgd(0.05),
        strategy=FedAvg(),
        datasets=datasets,
        batch_size=8,
        metrics=MetricManager((efficient.accuracy(),)),
        local_epochs=1,
        seed=5,
        observability=obs(),
        execution_mode=mode,
    )
    args.update(kwargs)
    return FederatedSimulation(**args)


def token_sim(module):
    """Three clients of a toy token classifier on the per-round driver: the
    benchmark cells' job at toy size."""
    rng = np.random.default_rng(0)
    datasets = []
    for n in (12, 20, 16):
        x = rng.integers(1, 50, (n, 8)).astype(np.int32)
        y = (x[:, 0] % N_CLASSES).astype(np.int32)
        datasets.append(ClientDataset(x[:n - 4], y[:n - 4],
                                      x[n - 4:], y[n - 4:]))
    return FederatedSimulation(
        logic=engine.ClientLogic(engine.from_flax(module),
                                 engine.masked_cross_entropy),
        tx=optax.sgd(0.005), strategy=FedAvg(), datasets=datasets,
        batch_size=4, metrics=MetricManager((efficient.accuracy(),)),
        local_steps=2, seed=3, execution_mode="pipelined",
        observability=obs())


@contextlib.contextmanager
def metadata_in_cache_key():
    """Compile with the ops' metadata in the persistent cache's key. JAX
    leaves it out by default, so an executable loaded from
    ``.jax_test_cache`` carries the name stacks of the tree that compiled
    it: a test of a NEW scope would read an older tree's names."""
    name = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, name)
    jax.config.update(name, True)
    try:
        yield
    finally:
        jax.config.update(name, before)


def compiled_texts(sim):
    """name -> optimised-HLO text of every round program ``fit()`` builds:
    what the introspector is asked about at build time, compiled here in
    its place."""
    texts = {}

    def compile_instead(name, jitted, args, **_):
        with metadata_in_cache_key():
            texts[name] = jitted.lower(
                *abstractify(args)).compile().as_text()

    sim.observability.introspector.introspect_jit = compile_instead
    sim.fit(1)
    return texts


def lowered_programs(sim, mode=EXEC_PIPELINED, n_rounds=1):
    """name -> the ``jax.stages.Lowered`` of every round program a
    ``fit(n_rounds)`` on ``mode`` asks the introspector about; nothing is
    compiled or run."""
    lowered = {}

    def lower_instead(name, jitted, args, **_):
        lowered[name] = jitted.lower(*abstractify(args))

    sim.observability.introspector.introspect_jit = lower_instead
    sim._introspect_programs(mode, n_rounds)
    return lowered


def family_module(family, **overrides):
    """A toy module of one of the cells' five model families."""
    if family == "transformer":
        return TransformerClassifier(**{**dict(
            vocab_size=50, n_classes=N_CLASSES, d_model=16, n_heads=2,
            n_layers=2, d_ff=32, max_len=8), **overrides})
    flash = functools.partial(flash_attention, causal=True, block_q=8,
                              block_k=8)
    if family == "deepseek":
        # one dense and one expert layer over a shared base, 4 of 8 experts
        # held, through the flash calls at two head widths
        return DeepseekV2Classifier(**{**dict(
            vocab_size=50, n_classes=N_CLASSES, d_model=16, n_layers=2,
            d_ff=32, n_heads=2, q_lora_rank=8, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, d_expert=8,
            n_routed_experts=8, experts_held=4, first_expert_held=2,
            n_group=4, topk_group=2, top_k=3, lora_rank=2, remat=True,
            dtype=jnp.bfloat16, attention_fn=flash), **overrides})
    if family == "nemotron":
        # a unit that repeats, then an attention and an expert block, over a
        # shared base: 4 of 8 experts held, grouped key/value heads through
        # the flash calls, the chunked scan over two chunks
        return NemotronHClassifier(**{**dict(
            vocab_size=50, n_classes=N_CLASSES, pattern="MEME*E", d_model=16,
            n_heads=4, n_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=4,
            ssm_groups=2, ssm_state=4, chunk=4, n_routed_experts=8,
            experts_held=4, first_expert_held=2, top_k=3,
            routed_scaling_factor=2.5, d_latent=8, d_expert=8, d_shared=16,
            lora_rank=2, remat=True, dtype=jnp.bfloat16, attention_fn=flash),
            **overrides})
    if family == "afmoe":
        # a dense and an expert layer under the window, then a full layer,
        # over a shared base: 4 of 8 experts held, grouped key/value heads
        # through the flash calls, a window of 5 under 8 positions
        return AfmoeClassifier(**{**dict(
            vocab_size=50, n_classes=N_CLASSES,
            layer_types=("sliding_attention", "sliding_attention",
                         "full_attention"),
            num_dense_layers=1, d_model=16, n_heads=4, n_kv_heads=2,
            head_dim=8, d_ff=32, d_expert=8, n_routed_experts=8,
            experts_held=4, first_expert_held=2, top_k=3, route_scale=2.5,
            sliding_window=5, lora_rank=2, remat=True, dtype=jnp.bfloat16,
            attention_fn=flash), **overrides})
    # one Mamba layer and one attention layer over a shared base
    return JambaClassifier(**{**dict(
        vocab_size=50, n_classes=N_CLASSES, d_model=16, n_layers=2,
        d_ff=32, n_heads=2, n_kv_heads=1, d_state=4, dt_rank=4,
        attn_layer_period=2, attn_layer_offset=1, lora_rank=2), **overrides})
