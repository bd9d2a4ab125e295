"""The single analytic-FLOP numerator rule.

`bench.py`'s analytic MFU arms and `tools/flash_crossover.py`'s crossover
model must never disagree about the same matmul. This module is the one
place the counting convention lives:

- a dot/matmul of result shape ``M x N`` contracting over ``K`` costs
  ``2*M*N*K`` flops (multiply + add, the ``FL4HEALTH_BENCH_ANALYTIC_FLOPS``
  convention and XLA ``HloCostAnalysis``'s rule);
- a training step costs 3x the forward pass (forward + ~2x backward).

No jax import — bench and the CLI tools import this before (or without)
a backend.
"""

from __future__ import annotations

# Backward pass ~= 2x forward for dense nets (dL/dx and dL/dW each cost a
# forward-sized matmul), so train = 3x forward. Shared by bench.py and
# tools/flash_crossover.py.
TRAIN_STEP_FLOP_MULTIPLIER = 3.0


def transformer_fwd_flops_per_token(
    d_model: int, d_ff: int, n_layers: int, seq: int
) -> float:
    """Forward flops per token of a standard pre-LN transformer block stack.

    Per layer: QKV+out projections ``8*d^2``, attention scores+values
    ``4*seq*d`` (two ``[seq,d]x[d,seq]``-shaped contractions per token),
    and the two MLP matmuls ``4*d*d_ff``.
    """
    return (8.0 * d_model * d_model + 4.0 * seq * d_model + 4.0 * d_model * d_ff) * n_layers


def transformer_round_flops(
    d_model: int,
    d_ff: int,
    n_layers: int,
    seq: int,
    n_clients: int,
    batch: int,
    local_steps: int,
) -> float:
    """Analytic flops of one federated round of transformer local training:
    train-step multiplier x per-token forward x tokens per step x steps x
    clients."""
    per_tok_fwd = transformer_fwd_flops_per_token(d_model, d_ff, n_layers, seq)
    return TRAIN_STEP_FLOP_MULTIPLIER * per_tok_fwd * seq * batch * local_steps * n_clients
