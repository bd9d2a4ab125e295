"""Long-context federated fine-tuning through the Pallas flash-attention
kernel (beyond-reference: the reference has NO long-context machinery —
SURVEY §5 — and delegates scale to DeepSpeed configs; here long context is
first-class: kernels/flash_attention.py carries the T^2 score memory in
VMEM, and on a multi-device seq mesh parallel/ring_attention.py's
ring_flash_attention extends the same kernel across chips).

This example trains a document-classifier cohort at seq_len 256 (tiny mode
shrinks it) with attention_fn=flash_attention inside the compiled
federated round — remat on, bf16-ready. On CPU the kernel runs in Pallas
interpret mode (slow but exact); on TPU it compiles via Mosaic.

Run:  python examples/long_context_example/run.py
Tiny: FL4HEALTH_EXAMPLE_TINY=1 FL4HEALTH_EXAMPLE_ROUNDS=1 FL4HEALTH_EXAMPLE_CLIENTS=2 python examples/long_context_example/run.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import optax  # noqa: E402

import _lib as lib  # noqa: E402
from fl4health_tpu.clients import engine  # noqa: E402

cfg = lib.example_config(Path(__file__).parent)

import functools
import os

if os.environ.get("FL4HEALTH_EXAMPLE_TINY"):
    # smoke-suite budget: interpret-mode flash at seq 256 is too slow for
    # the CPU lane; keep the code path, shrink the shapes (interpret mode
    # takes any block size — the compiled kernel needs multiples of 128,
    # which is what config.yaml ships)
    cfg.update(seq_len=32, vocab_size=64, d_model=16, n_heads=2, n_layers=1,
               d_ff=32, block=16, local_steps=2)

import jax
from fl4health_tpu.datasets.synthetic import synthetic_text_classification
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.models.transformer import TransformerClassifier
from fl4health_tpu.server.simulation import ClientDataset, FederatedSimulation
from fl4health_tpu.strategies.fedavg import FedAvg

module = TransformerClassifier(
    vocab_size=cfg["vocab_size"], n_classes=cfg["n_classes"],
    d_model=cfg["d_model"], n_heads=cfg["n_heads"], n_layers=cfg["n_layers"],
    d_ff=cfg["d_ff"], max_len=cfg["seq_len"], remat=True,
    attention_fn=functools.partial(
        flash_attention, block_q=cfg["block"], block_k=cfg["block"]
    ),
)
datasets = []
for i in range(cfg["n_clients"]):
    x, y = synthetic_text_classification(
        jax.random.PRNGKey(30 + i), 24, cfg["vocab_size"], cfg["seq_len"],
        cfg["n_classes"], class_sep=3.0,
    )
    datasets.append(ClientDataset(x[:16], y[:16], x[16:], y[16:]))

sim = FederatedSimulation(
    logic=engine.ClientLogic(engine.from_flax(module),
                             engine.masked_cross_entropy),
    tx=optax.adam(cfg["learning_rate"]),
    strategy=FedAvg(),
    datasets=datasets,
    batch_size=cfg["batch_size"],
    metrics=lib.accuracy_metrics(),
    local_steps=cfg["local_steps"],
    seed=23,
)
lib.run_and_report(sim, cfg)
