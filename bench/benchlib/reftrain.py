"""The first training steps of a plain reference, and what is compared.

``reference_steps`` runs a reference module (``bench/reference/<name>.py``)
through the first steps of training as a configuration states them: mean
next-token cross-entropy over the whole batch, plus the mean over rows of
the reference's ``extra_loss`` where it defines one (such as a router's
sequence-wise auxiliary loss), its gradient clipped to a global norm,
AdamW with decoupled weight decay on every stored leaf of two or more
dimensions, the learning rate warmed up linearly, and the
parameters stored in the configuration's type after each update.  The
arithmetic is float32 at the highest matmul precision; the control passes
a ``q`` that rounds every matmul operand to a lower precision.

The batch goes through a row at a time, so the reference fits on one chip
beside nothing else: gradients are summed over the rows and divided by the
batch's token count.

A reference module defines ``init_params(key, m)``, ``hidden(params,
tokens, m, q)`` and ``head_matrix(params, m)``, and may define
``extra_loss(params, tokens, m, q)``: the term of each row of ``tokens``
that the program adds to the cross-entropy it differentiates.

What it returns, and what the run reads from the program, is a
``Readings``: the loss of each step (the cross-entropy alone, as the
program reports it), the norm of each leaf of the first
step's gradient as the optimizer gets it, and the norm of each leaf's
change over the steps.  ``gaps`` compares two of them.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import statistics
import sys
from pathlib import Path
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.spec import ROOT, load_module


def load_reference(name: str, root: Path = ROOT):
    """The module ``bench/reference/<name>.py`` under ``root``; the
    modules beside it (``lm_common``) are importable by name."""
    ref_dir = Path(root) / "bench" / "reference"
    if str(ref_dir) not in sys.path:
        sys.path.insert(0, str(ref_dir))
    return load_module(ref_dir / f"{name}.py")


def leaf_paths(tree) -> List[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                        - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


@dataclasses.dataclass
class Readings:
    losses: List[float]
    grad_norms: Dict[str, float]     # leaf -> norm of step 1's gradient
    change_norms: Dict[str, float]   # leaf -> norm of its change


def warmup_lr(step: int, opt) -> float:
    """The warm-up branch of the schedule; the steps read lie inside it."""
    assert step < opt["warmup_steps"], (step, opt["warmup_steps"])
    return opt["peak_lr"] * (step + 1) / max(opt["warmup_steps"], 1)


def identity(x):
    return x


def ce_sum(logits, labels, vocab):
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < vocab, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def fp8_round(x):
    """``x`` rounded to the 3 mantissa bits of float8 e4m3, ties to even,
    its exponent left as it is (a perfectly scaled fp8 tensor)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) & jnp.uint32(
        0xFFF00000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@jax.custom_vjp
def fp8(x):
    """The control's precision: operands and their gradients in fp8."""
    return fp8_round(x)


fp8.defvjp(lambda x: (fp8_round(x), None), lambda _, g: (fp8_round(g),))

def _programs(ref, m, opt, q):
    """The reference's jitted block gradient and update, built once per
    (reference, sizes, optimizer, precision)."""
    key = (ref, _Frozen(m), _Frozen(opt), q)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    store = jnp.dtype(m["param_dtype"])
    extra_loss = getattr(ref, "extra_loss", None)

    def block_loss(params, tokens, labels):
        """(what is differentiated, the cross-entropy), summed over the
        block's tokens."""
        h = ref.hidden(params, tokens, m, q)
        logits = q(q(h) @ q(ref.head_matrix(params, m)))
        ce = ce_sum(logits, labels, m["vocab_size"])
        if extra_loss is None:
            return ce, ce
        # a row's term counted once per token: over the batch's token
        # count, the sum over rows is the mean over rows
        extra = jnp.sum(extra_loss(params, tokens, m, q)) * tokens.shape[1]
        return ce + extra, ce

    @functools.partial(jax.jit, donate_argnums=(1,))
    def accumulate(params, acc, tokens, labels):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        (_, loss), g = jax.value_and_grad(block_loss, has_aux=True)(
            p32, tokens, labels)
        return jax.tree.map(jnp.add, acc, g), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(params, mom, vel, grads, count, lr, t):
        grads = jax.tree.map(lambda g: g / count, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["max_grad_norm"] / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2 = opt["b1"], opt["b2"]
        c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def one(g, mo, ve, x):
            x32 = x.astype(jnp.float32)
            mo = b1 * mo + (1 - b1) * g
            ve = b2 * ve + (1 - b2) * g * g
            delta = (mo / c1) / (jnp.sqrt(ve / c2) + opt["eps"])
            if x.ndim >= 2:
                delta = delta + opt["weight_decay"] * x32
            # returned in the stored type: a cast that stays inside the
            # program may be dropped by the compiler (excess precision)
            return (x32 - lr * delta).astype(store), mo, ve

        out = jax.tree.map(one, grads, mom, vel, params)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2), grads

    init = jax.jit(ref.init_params, static_argnums=1)
    _PROGRAMS[key] = (init, accumulate, update)
    return _PROGRAMS[key]


_PROGRAMS: Dict[tuple, tuple] = {}


def reference_steps(ref, m, opt, batches, key, q: Callable = identity,
                    keep_rows: int = 0) -> Readings:
    """Train ``ref`` from ``key``'s weights over ``batches`` (a list of
    {"tokens", "labels"} host arrays), one step per batch.

    ``keep_rows`` > 0 uses only that many rows of each batch, the mean
    taken over them: a planted fault, for reading what it does."""
    init, accumulate, update = _programs(ref, m, opt, q)
    p0 = init(key, _Frozen(m))
    paths = leaf_paths(p0)
    p = jax.tree.map(lambda x: jnp.array(x, copy=True), p0)
    mom = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    vel = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches):
            tokens, labels = batch["tokens"], batch["labels"]
            if keep_rows:
                tokens, labels = tokens[:keep_rows], labels[:keep_rows]
            acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
            total = 0.0
            for r in range(tokens.shape[0]):
                acc, loss = accumulate(p, acc, tokens[r:r + 1],
                                       labels[r:r + 1])
                total += float(loss)
            count = float(tokens.size)
            losses.append(total / count)
            p, mom, vel, grads = update(p, mom, vel, acc, count,
                                        warmup_lr(step, opt),
                                        float(step + 1))
            del acc
            if step == 0:
                grad_norms = dict(zip(paths, map(float, leaf_norms(grads))))
            del grads
    changes = dict(zip(paths, map(float, change_norms(p, p0))))
    return Readings(losses, grad_norms, changes)


class _Frozen(dict):
    """A hashable view of the model's sizes, for ``static_argnums``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def excluded_leaves(ref: Readings) -> List[str]:
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's.  Adam moves them by round-off."""
    med = statistics.median(ref.grad_norms.values())
    return sorted(k for k, v in ref.grad_norms.items() if v < 1e-3 * med)


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              skip=()) -> Dict[str, float]:
    """Each leaf's |got - want| over the larger of want and the median."""
    keys = [k for k in want if k not in skip]
    med = statistics.median(want[k] for k in keys)
    out = {}
    for k in keys:
        gap = abs(got.get(k, math.inf) - want[k]) / max(want[k], med)
        out[k] = gap if math.isfinite(gap) else math.inf
    return out


def gaps(got: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers a configuration may compare.

    ``loss_gap`` is the largest relative gap of the steps' losses and
    ``loss_gap_first`` that of the first step alone; ``grad_norm_gap`` and
    ``update_norm_gap`` are the worst leaf's gap of norms (the first
    gradient, the change over the steps), ``..._median`` the median
    leaf's.  Leaves whose reference gradient is nought to rounding are left
    out of the change."""
    finite = len(got.losses) == len(ref.losses) and all(
        math.isfinite(x) for x in got.losses)
    rel = [abs(a - b) / abs(b) for a, b in zip(got.losses, ref.losses)]
    out = {"loss_gap": max(rel) if finite else math.inf,
           "loss_gap_first": rel[0] if finite else math.inf}
    for name, g, w, skip in (
            ("grad_norm_gap", got.grad_norms, ref.grad_norms, ()),
            ("update_norm_gap", got.change_norms, ref.change_norms,
             excluded_leaves(ref))):
        if set(g) != set(w):
            out[name] = out[name + "_median"] = math.inf
            continue
        per_leaf = sorted(leaf_gaps(g, w, skip).values())
        out[name] = per_leaf[-1]
        out[name + "_median"] = statistics.median(per_leaf)
    return out


def to_host(batch) -> Dict[str, np.ndarray]:
    return {k: np.array(v) for k, v in batch.items()}
