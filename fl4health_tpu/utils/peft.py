"""PEFT / LoRA parameter filtering — the pytree equivalent of peft adapters.

Parity surface (/root/reference/fl4health/utils/peft_parameter_extraction.py:7
``get_all_peft_parameters_from_model``: collects the adapter-injected
parameters from a HF peft model so only they cross the wire;
/root/reference/examples/fedllm_example trains LoRA adapters federally).

TPU-native design: adapters are ordinary params named ``lora_a``/``lora_b``
(models/transformer.py LoraDense). "PEFT" is ONE predicate over parameter
paths, :func:`per_client_predicate` (a leaf is per client when a whole
segment of its path is a marker), used two ways:

- **a base that exists once** (the scalable form): the module says it —
  ``def per_client_param(self, path): return per_client_predicate()(path)``,
  which ``engine.from_flax`` reads — and the frozen base is held once in the
  server state while clients hold, train and exchange the adapters alone
  (clients/engine.py ``ModelDef.per_client``, strategies/shared_base.py);
- **whole-model clients** (every client keeps a frozen copy of the base):
  the same predicate seen as an exchanger filter (``lora_exchanger()``, what
  crosses the wire) and as an optimizer mask (``lora_trainable_mask`` +
  ``masked_optimizer``, what trains locally). The two forms train the same
  adapters (tests/server/test_shared_params.py).

No module surgery, no adapter classes: path predicates compose with every
existing exchanger/strategy because the param structure never changes.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import optax

from fl4health_tpu.core import pytree as ptu
from fl4health_tpu.core.types import Params
from fl4health_tpu.exchange.exchanger import FixedLayerExchanger

# Path segments that mark PEFT-trainable leaves: the LoRA factors plus the
# task head (peft convention: `modules_to_save=["classifier"]`).
LORA_MARKERS: tuple[str, ...] = ("lora_a", "lora_b", "classifier")


def per_client_predicate(
    markers: Sequence[str] = LORA_MARKERS,
) -> Callable[[str], bool]:
    """THE predicate: dotted path -> True for a PEFT leaf (adapters + head).
    Matches whole path SEGMENTS, not raw substrings: a module merely named
    "aux_classifier_head" is not a marker's."""
    marks = frozenset(markers)
    return lambda path: not marks.isdisjoint(path.split("."))


def peft_parameter_paths(params: Params, markers: Sequence[str] = LORA_MARKERS) -> list[str]:
    """Dotted paths of all PEFT parameters (get_all_peft_parameters_from_model
    equivalent — returns paths rather than tensors because pytree leaves are
    addressed, not owned)."""
    is_peft = per_client_predicate(markers)
    return [path for path in ptu.leaf_paths(params) if is_peft(path)]


def lora_exchanger(markers: Sequence[str] = LORA_MARKERS) -> FixedLayerExchanger:
    """Wire filter: only adapters (+ head) cross the wire — the federated
    LoRA exchange the fedllm example gets from peft's state-dict filtering.

    A view of :func:`per_client_predicate`, so a module merely named
    "aux_classifier_head" cannot leak onto the wire while staying frozen
    locally.
    """
    return FixedLayerExchanger(include=per_client_predicate(markers))


def lora_trainable_mask(params: Params, markers: Sequence[str] = LORA_MARKERS):
    """Bool pytree: True where the leaf should train (adapters + head)."""
    return ptu.select_by_path(params, per_client_predicate(markers))


def masked_optimizer(
    tx: optax.GradientTransformation, trainable_mask
) -> optax.GradientTransformation:
    """Freeze untrainable leaves: real updates where mask is True, zeros
    elsewhere (optax.multi_transform over the bool mask). The frozen base
    weights still live in params, so exchangers/checkpointers see the full
    model."""
    labels = jax.tree_util.tree_map(
        lambda t: "train" if t else "freeze", trainable_mask
    )
    return optax.multi_transform(
        {"train": tx, "freeze": optax.set_to_zero()}, labels
    )
