"""Step functions: the jash payloads of the PoUW training/serving system.

``make_train_step(cfg)`` returns a pure
``(state, batch) -> (state, metrics)`` function — *this is what the
Runtime Authority publishes per block* for the training use case
(PNPCoin §1: "finding the next optimum in hyperdimensional SGD").
``make_prefill_step`` / ``make_decode_step`` are the serving analogues.

All of them are bounded-complexity by construction (jaxpr has no
``while_loop`` — see ``core/jash.py``), deterministic, and shardable
under pjit on the production mesh.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import InputShape, ModelConfig
from repro.models.model import adapt_for_shape, build_model, cache_len_for
from repro.optim.adamw import AdamWState, adamw_init, adamw_update
from repro.optim.schedule import cosine_schedule
from repro.sharding.partition import gather_tree_async


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


# ---------------------------------------------------------------------------
# digest-stable state canonicalization
# ---------------------------------------------------------------------------


def _canonical_leaf(arr: np.ndarray) -> np.ndarray:
    """Little-endian, C-contiguous view of ``arr`` — the only byte order
    a digest may ever see, regardless of host endianness or the device
    layout the array came back from.  Copies only where it must; a 0-d
    leaf comes back with shape ``(1,)``, as the committed framing has it."""
    if arr.dtype.str.startswith(">"):
        arr = arr.astype(arr.dtype.newbyteorder("<"))
    return np.ascontiguousarray(arr)


def _leaf_header(path, arr: np.ndarray) -> bytes:
    """The framing in front of a leaf's data: ``path | dtype | ndim |
    shape``.

    The path prefix keeps structurally-different trees with identical
    flattened values apart; the dtype+shape frame keeps reinterpreted
    buffers apart (``float32[4]`` never collides with ``uint8[16]``)."""
    pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)
    return (pstr.encode() + b"\x00" + arr.dtype.str.encode() + b"\x00"
            + np.int64(arr.ndim).tobytes()
            + np.asarray(arr.shape, np.int64).tobytes())


def tree_digest(tree: Any) -> str:
    """sha256 hex digest of the canonical bytes of ``tree`` — the
    generic bit-exact commitment for any value pytree (params, batches,
    metric stacks).  Deterministic across processes, platforms, and
    shardings.

    The stream is every leaf's header and its little-endian C-order
    data, leaf by leaf in flatten order.  Every device leaf's copy to the
    host starts up front (``sharding.partition.gather_tree_async``), so
    later leaves cross while ``hashlib``, which drops the GIL, hashes
    earlier ones in place through a ``uint8`` view."""
    h = hashlib.sha256()
    for path, leaf in gather_tree_async(tree):
        # only the wait for bytes still in flight is exposed here
        with spans.span("tree_digest.fetch"):
            arr = np.asarray(leaf)
            if isinstance(leaf, jax.Array):
                spans.count("d2h_bytes", arr.nbytes)
        with spans.span("tree_digest.hash"):
            arr = _canonical_leaf(arr)
            header = _leaf_header(path, arr)
            h.update(header)
            h.update(arr.reshape(-1).view(np.uint8))
            spans.count("hashed_bytes", len(header) + arr.nbytes)
    return h.hexdigest()


def params_digest(state_or_params: Any) -> str:
    """The chain's ``state_digest`` for model training: sha256 of the
    canonical params bytes.  Accepts a ``TrainState`` (digests its
    ``params``) or a bare params pytree.  Shared by ``PoUWTrainer`` and
    ``ModelTrainingWorkload`` so both commit the same digest for the
    same weights."""
    params = (state_or_params.params
              if isinstance(state_or_params, TrainState) else state_or_params)
    with spans.span("params_digest"):
        return tree_digest(params)


@dataclasses.dataclass(frozen=True)
class TrainHparams:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0


def make_train_state(cfg: ModelConfig, key) -> TrainState:
    model = build_model(cfg)
    params = model.init(key)
    return TrainState(params=params,
                      opt=adamw_init(params, jnp.dtype(cfg.opt_dtype)))


def make_train_step(cfg: ModelConfig,
                    hp: TrainHparams = TrainHparams()
                    ) -> Callable[[TrainState, Dict], Tuple[TrainState, Dict]]:
    model = build_model(cfg)

    def train_step(state: TrainState, batch: Dict) -> Tuple[TrainState, Dict]:
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(state.params, batch)
        lr = cosine_schedule(state.opt.step + 1, peak_lr=hp.peak_lr,
                             warmup_steps=hp.warmup_steps,
                             total_steps=hp.total_steps)
        params, opt = adamw_update(state.params, grads, state.opt, lr,
                                   weight_decay=hp.weight_decay,
                                   grad_clip=hp.grad_clip)
        out = {"loss": loss, "ce": metrics["ce"], "aux": metrics["aux"],
               "lr": lr}
        return TrainState(params=params, opt=opt), out

    return train_step


def make_eval_step(cfg: ModelConfig):
    """Forward-only loss (used by optimal-mode / ES candidate scoring)."""
    model = build_model(cfg)

    def eval_step(params, batch) -> jax.Array:
        loss, _ = model.loss(params, batch)
        return loss

    return eval_step


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def make_prefill_step(cfg: ModelConfig, shape: InputShape):
    cfg = adapt_for_shape(cfg, shape)
    model = build_model(cfg)

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache)

    return prefill_step


def make_decode_step(cfg: ModelConfig, shape: InputShape):
    """One new token against a ``shape.seq_len``-deep cache."""
    cfg = adapt_for_shape(cfg, shape)
    model = build_model(cfg)

    def decode_step(params, batch, cache):
        logits, new_cache = model.decode_step(params, batch, cache)
        next_tok = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)
        return next_tok.astype(jnp.int32), logits, new_cache

    return decode_step


def make_init_cache(cfg: ModelConfig, shape: InputShape):
    cfg = adapt_for_shape(cfg, shape)
    model = build_model(cfg)

    def init_cache():
        return model.init_cache(shape.global_batch, cache_len_for(cfg, shape))

    return init_cache
