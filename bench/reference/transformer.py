"""Plain float32 reference of the chain's model training: a Qwen3-style
decoder (GQA, qk-norm, rope, gated SiLU MLP, RMSNorm, tied head), its
cross-entropy, AdamW with global-norm clipping and a warmup-cosine
schedule, and the synthetic token stream the chain trains on.

What it follows of the program is only what the configuration states:
the same seeded initial weights (normal, 1/sqrt(fan_in); embedding
0.02), the same (seed, height, microstep)-keyed batches, and parameters
stored in the configuration's dtype between steps (bfloat16 for the
matrices, float32 for norm gains).  Every operation in between runs in
float32 with ``Precision.HIGHEST`` matmuls.  Departures: none in the
mathematics; layers are scanned with ``jax.checkpoint`` so that the
reference fits on one chip beside nothing else.

``matmul_dtype`` names the control: every matmul's inputs are rounded to
that dtype first (``float8_e4m3fn`` is the step below bfloat16); with
``bfloat16``, the program's own precision, it is a second witness.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    layers: int
    vocab: int
    theta: float
    eps: float
    param_dtype: str
    seq: int
    batch: int

    @classmethod
    def of(cls, cfg: dict, traffic: dict) -> "Shape":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, heads=h, kv=cfg["num_key_value_heads"],
                   hd=cfg.get("head_dim") or d // h,
                   ff=cfg["intermediate_size"],
                   layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   param_dtype=cfg["run"]["param_dtype"],
                   seq=traffic["seq_len"], batch=traffic["batch"])

    @property
    def padded_vocab(self) -> int:
        return 128 * math.ceil(self.vocab / 128)


# ---------------------------------------------------------------------------
# weights and data from the seed
# ---------------------------------------------------------------------------


def init_params(sh: Shape, seed: int) -> Dict:
    """The chain's initial weights for ``seed``, in the stored dtypes."""
    dt = jnp.dtype(sh.param_dtype)
    L = sh.layers

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dt)

    keys = jax.random.split(jax.random.key(seed), 8)
    k_attn, k_mlp = jax.random.split(jax.random.fold_in(keys[2], 0))
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    f32z = lambda *s: jnp.zeros(s, jnp.float32)
    return {
        "embed": normal(keys[0], (sh.padded_vocab, sh.d), 0.02),
        "ln_f": f32z(sh.d),
        "groups": {"l0": {
            "ln1": f32z(L, sh.d),
            "ln2": f32z(L, sh.d),
            "attn": {
                "wq": normal(ka[0], (L, sh.d, sh.heads * sh.hd),
                             1 / np.sqrt(sh.d)),
                "wk": normal(ka[1], (L, sh.d, sh.kv * sh.hd),
                             1 / np.sqrt(sh.d)),
                "wv": normal(ka[2], (L, sh.d, sh.kv * sh.hd),
                             1 / np.sqrt(sh.d)),
                "wo": normal(ka[3], (L, sh.heads * sh.hd, sh.d),
                             1 / np.sqrt(sh.heads * sh.hd)),
                "q_norm": f32z(L, sh.hd),
                "k_norm": f32z(L, sh.hd),
            },
            "mlp": {
                "w1": normal(km[0], (L, sh.d, sh.ff), 1 / np.sqrt(sh.d)),
                "w3": normal(km[1], (L, sh.d, sh.ff), 1 / np.sqrt(sh.d)),
                "w2": normal(km[2], (L, sh.ff, sh.d), 1 / np.sqrt(sh.ff)),
            },
        }},
    }


def batch(sh: Shape, seed: int, height, micro, rows: Optional[int] = None
          ) -> Dict:
    """Microbatch ``micro`` of block ``height``: a Markov-ish token stream
    (cumulative drift x 31 + a base, mod vocab) with 5% uniform noise;
    labels are the tokens shifted left, the last one masked (-1)."""
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(seed), height), micro)
    k1, k2, k3 = jax.random.split(key, 3)
    B, S, V = sh.batch, sh.seq, sh.vocab
    base = jax.random.randint(k1, (B, 1), 0, V)
    drift = jax.random.randint(k2, (B, S), 0, 16)
    toks = jnp.cumsum(drift, axis=1) * 31 + base
    noise = jax.random.randint(k3, (B, S), 0, V)
    mix = jax.random.bernoulli(k3, 0.05, (B, S))
    tokens = jnp.where(mix, noise, jnp.mod(toks, V)).astype(jnp.int32)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((B, 1), -1, jnp.int32)], axis=1)
    return {"tokens": tokens[:rows], "labels": labels[:rows]}


_init_jit = jax.jit(init_params, static_argnums=0)


# ---------------------------------------------------------------------------
# forward and loss, float32
# ---------------------------------------------------------------------------


def _rounder(low):
    """Round to ``low`` with one scale per tensor (its largest magnitude
    maps to the format's largest), forward and, for the cotangent, in
    the backward pass: how a step computed in that format treats both
    a matmul's inputs and its gradients.  A format with float32's range
    (bfloat16) is rounded to unscaled."""
    top = float(jnp.finfo(low).max)

    def rnd(x):
        if top > float(jnp.finfo(jnp.bfloat16).max) / 2:
            return x.astype(low).astype(jnp.float32)
        s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return (x * s).astype(low).astype(jnp.float32) / s

    @jax.custom_vjp
    def q(x):
        return rnd(x)

    q.defvjp(lambda x: (rnd(x), None), lambda _, g: (rnd(g),))
    return q


def _mm_fn(matmul_dtype: Optional[str]):
    q = _rounder(jnp.dtype(matmul_dtype)) if matmul_dtype else None

    def mm(spec, a, b):
        if q is not None:
            a, b = q(a), q(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return mm


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, theta):
    """x: (B, S, H, hd); rotate the two halves of each head."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss_fn(sh: Shape, matmul_dtype: Optional[str] = None):
    mm = _mm_fn(matmul_dtype)
    G = sh.heads // sh.kv

    def layer(x, lp):
        B, S, _ = x.shape
        a = lp["attn"]
        h = _rms(x, lp["ln1"], sh.eps)
        q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, sh.heads, sh.hd)
        k = mm("bsd,de->bse", h, a["wk"]).reshape(B, S, sh.kv, sh.hd)
        v = mm("bsd,de->bse", h, a["wv"]).reshape(B, S, sh.kv, sh.hd)
        q = _rope(_rms(q, a["q_norm"], sh.eps), sh.theta)
        k = _rope(_rms(k, a["k_norm"], sh.eps), sh.theta)
        # query head i reads key/value head i // G
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(sh.hd))
        causal = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(causal[None, None], scores, -1e9)
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bhqk,bkhd->bqhd", probs, v).reshape(B, S, sh.heads * sh.hd)
        x = x + mm("bse,ed->bsd", o, a["wo"])
        m = lp["mlp"]
        h = _rms(x, lp["ln2"], sh.eps)
        gate = mm("bsd,df->bsf", h, m["w1"])
        up = mm("bsd,df->bsf", h, m["w3"])
        x = x + mm("bsf,fd->bsd", gate * jax.nn.sigmoid(gate) * up, m["w2"])
        return x, None

    def loss(params, tokens, labels):
        p = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        x = p["embed"][tokens]
        x, _ = jax.lax.scan(jax.checkpoint(layer), x, p["groups"]["l0"])
        x = _rms(x, p["ln_f"], sh.eps)
        logits = mm("bsd,vd->bsv", x, p["embed"])
        if sh.padded_vocab != sh.vocab:
            logits = jnp.where(jnp.arange(sh.padded_vocab) < sh.vocab,
                               logits, -1e9)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
        valid = (labels >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * valid) / jnp.maximum(valid.sum(), 1.0)

    return loss


# ---------------------------------------------------------------------------
# AdamW step
# ---------------------------------------------------------------------------


def lr_at(hp: dict, step):
    """Linear warmup to ``peak_lr``, then cosine to a tenth of it."""
    step = jnp.asarray(step, jnp.float32)
    warm = hp["peak_lr"] * step / max(hp["warmup_steps"], 1)
    frac = jnp.clip((step - hp["warmup_steps"])
                    / max(hp["total_steps"] - hp["warmup_steps"], 1), 0, 1)
    cos = hp["peak_lr"] * (0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < hp["warmup_steps"], warm, cos)


_STEPS: Dict = {}


def make_step(sh: Shape, hp: dict, matmul_dtype: Optional[str] = None,
              rows: Optional[int] = None):
    key = (sh, tuple(sorted(hp.items())), matmul_dtype, rows)
    if key not in _STEPS:
        _STEPS[key] = _make_step(sh, hp, matmul_dtype, rows)
    return _STEPS[key]


def _make_step(sh: Shape, hp: dict, matmul_dtype: Optional[str],
               rows: Optional[int]):
    loss = loss_fn(sh, matmul_dtype)
    b1, b2, eps = hp["adam_b1"], hp["adam_b2"], hp["adam_eps"]

    def step(params, m, v, t, seed, height, micro):
        bt = batch(sh, seed, height, micro, rows)
        value, grads = jax.value_and_grad(loss)(params, bt["tokens"],
                                                bt["labels"])
        t = t + 1
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        lr = lr_at(hp, t)
        bc1 = 1.0 - b1 ** t.astype(jnp.float32)
        bc2 = 1.0 - b2 ** t.astype(jnp.float32)

        def upd(p, g, mi, vi):
            g = g * scale
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * jnp.square(g)
            delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
            p32 = p.astype(jnp.float32)
            if p.ndim >= 2:
                delta = delta + hp["weight_decay"] * p32
            return (p32 - lr * delta).astype(p.dtype), mi, vi

        out = jax.tree.map(upd, params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2), t, value

    return jax.jit(step, donate_argnums=(0, 1, 2))


def leaf_norms(tree) -> Dict[str, float]:
    """Frobenius norm of every leaf, float32, by path."""
    from bench.reference.commit import flatten
    norms = _norms(tree)
    return {path: float(v) for path, v in flatten(jax.device_get(norms))}


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def change_norms(after: Dict, before: Dict) -> Dict[str, float]:
    """Per-leaf norm of ``after - before``; ``after`` may be on the host,
    it goes to the device one leaf at a time."""
    from bench.reference.commit import flatten
    b = dict(flatten(before))
    return {path: float(_diff_norm(jnp.asarray(leaf), b[path]))
            for path, leaf in flatten(after)}


@dataclasses.dataclass
class Readings:
    """What the comparison reads of one training run: the loss each of
    the first blocks reports (its last microstep's), the first moment
    after block 1 by leaf, and each leaf's change after the last block."""
    losses: List[float]
    moments: Dict[str, float]
    changes: Dict[str, float]


FAULTS = ("half_batch", "altered_loss")


def run(sh: Shape, hp: dict, seed: int, microsteps: int, blocks: int,
        matmul_dtype: Optional[str] = None, fault: Optional[str] = None):
    """Train ``blocks`` blocks of ``microsteps`` steps from the seed.
    Returns the readings and the initial weights (kept on the device).

    ``fault`` plants one of the faults the comparison must catch, for
    reading its size at the cell's own shapes: ``half_batch`` trains on
    the first half of each microbatch's rows; ``altered_loss`` reports
    each block's loss 0.1% high."""
    rows = sh.batch // 2 if fault == "half_batch" else None
    step = make_step(sh, hp, matmul_dtype, rows)
    p0 = _init_jit(sh, jnp.uint32(seed))
    params = jax.tree.map(jnp.copy, p0)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    t = jnp.int32(0)
    losses, moments = [], {}
    for h in range(blocks):
        for mi in range(microsteps):
            params, m, v, t, value = step(params, m, v, t, jnp.uint32(seed),
                                          jnp.int32(h), jnp.int32(mi))
        losses.append(float(value) * (1.001 if fault == "altered_loss"
                                      else 1.0))
        if h == 0:
            moments = leaf_norms(m)
    del m, v
    changes = change_norms(params, p0)
    del params
    return Readings(losses, moments, changes), p0


def leaf_gaps(a: Dict, b: Dict, counted) -> Dict[str, float]:
    """|a - b| over max(b, the median of b) for each counted leaf."""
    med = float(np.median([b[k] for k in counted]))
    return {k: abs(a[k] - b[k]) / max(b[k], med) for k in counted}


def compare(got: Readings, want: Readings, loss_blocks: int,
            floor: float = 1e-3) -> Dict:
    """The numbers ``correct`` reads.

    * ``loss_gap``: largest |loss - reference loss| over the first
      ``loss_blocks`` blocks;
    * ``moment_gap``, ``update_gap``: worst leaf of |norm - reference
      norm| over max(reference norm, the median leaf's reference norm).

    Leaves whose reference first moment is under ``floor`` times the
    median leaf's are left out of both: their gradient is nought to
    rounding (none is at these shapes; the rule is by value, not name).
    """
    med_m = float(np.median(list(want.moments.values())))
    counted = [k for k, x in want.moments.items() if x >= floor * med_m]

    def worst(a: Dict, b: Dict) -> float:
        return max(leaf_gaps(a, b, counted).values())

    return {
        "loss_gap": max(abs(x - y) for x, y in
                        zip(got.losses[:loss_blocks], want.losses)),
        "moment_gap": worst(got.moments, want.moments),
        "update_gap": worst(got.changes, want.changes),
    }
