"""Loss functions — parity with the reference criterion zoo
(dl/src/main/scala/com/intel/analytics/bigdl/nn/*Criterion*.scala).

Class labels are 0-based integer arrays (the reference uses Lua 1-based).
All losses are pure functions of (input, target); gradients come from
jax.grad — there are no updateGradInput implementations to maintain.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.core.criterion import Criterion

__all__ = [
    "ClassNLLCriterion", "LabelSmoothingNLLCriterion",
    "MSECriterion", "AbsCriterion", "BCECriterion",
    "CrossEntropyCriterion", "ClassSimplexCriterion", "DistKLDivCriterion",
    "CosineEmbeddingCriterion", "HingeEmbeddingCriterion",
    "L1HingeEmbeddingCriterion", "MarginCriterion", "MarginRankingCriterion",
    "MultiCriterion", "ParallelCriterion", "MultiLabelMarginCriterion",
    "MultiLabelSoftMarginCriterion", "MultiMarginCriterion",
    "SmoothL1Criterion", "SoftMarginCriterion", "L1Cost", "L1Penalty",
    "TimeDistributedCriterion", "MultiTokenNLLCriterion",
]


def _one_hot(target, n, dtype):
    return jax.nn.one_hot(target.astype(jnp.int32), n, dtype=dtype)


class ClassNLLCriterion(Criterion):
    """Negative log-likelihood over log-probability inputs
    (reference nn/ClassNLLCriterion.scala; its per-sample threading is
    irrelevant under XLA). Input: (B, C) log-probs (e.g. from LogSoftMax);
    target: (B,) int labels. Optional per-class weights."""

    def __init__(self, weights: Optional[jnp.ndarray] = None,
                 size_average: bool = True):
        super().__init__(size_average)
        self.weights = weights

    def forward(self, input, target):
        t = target.astype(jnp.int32)
        ll = jnp.take_along_axis(input, t[:, None], axis=1)[:, 0]
        if self.weights is not None:
            w = jnp.take(self.weights.astype(input.dtype), t)
            loss = -(w * ll)
            if self.size_average:
                return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
            return jnp.sum(loss)
        return self._reduce(-ll)


class LabelSmoothingNLLCriterion(Criterion):
    """NLL over log-probs with uniform label smoothing: the target
    distribution is (1-eps) on the true class + eps/C elsewhere — the
    standard ImageNet recipe refinement (beyond the reference's
    ClassNLLCriterion; composes with LogSoftMax the same way)."""

    def __init__(self, smoothing: float = 0.1, size_average: bool = True):
        super().__init__(size_average)
        if not 0.0 <= smoothing < 1.0:
            raise ValueError(f"smoothing {smoothing} not in [0, 1)")
        self.smoothing = smoothing

    def forward(self, input, target):
        t = target.astype(jnp.int32)
        # smoothing mass eps spreads uniformly: eps * mean(logp) term
        ll_true = jnp.take_along_axis(input, t[:, None], axis=1)[:, 0]
        ll_mean = jnp.mean(input, axis=-1)
        eps = self.smoothing
        loss = -((1.0 - eps) * ll_true + eps * ll_mean)
        return self._reduce(loss)


class MSECriterion(Criterion):
    """(reference nn/MSECriterion.scala)"""

    def forward(self, input, target):
        return self._reduce(jnp.square(input - target))


class AbsCriterion(Criterion):
    """(reference nn/AbsCriterion.scala)"""

    def forward(self, input, target):
        return self._reduce(jnp.abs(input - target))


class BCECriterion(Criterion):
    """Binary cross entropy over probabilities in (0,1)
    (reference nn/BCECriterion.scala), with the standard eps clamp."""

    def __init__(self, weights: Optional[jnp.ndarray] = None,
                 size_average: bool = True, eps: float = 1e-12):
        super().__init__(size_average)
        self.weights = weights
        self.eps = eps

    def forward(self, input, target):
        p = jnp.clip(input, self.eps, 1.0 - self.eps)
        per = -(target * jnp.log(p) + (1.0 - target) * jnp.log1p(-p))
        if self.weights is not None:
            per = per * self.weights.astype(per.dtype)
        return self._reduce(per)


class CrossEntropyCriterion(Criterion):
    """LogSoftMax + ClassNLL fused (reference nn/CrossEntropyCriterion.scala).
    Input: (B, C) raw logits; target: (B,) int labels. The fused form is both
    the reference's composition and the numerically-stable XLA-friendly one."""

    def __init__(self, weights: Optional[jnp.ndarray] = None,
                 size_average: bool = True):
        super().__init__(size_average)
        self.weights = weights

    def forward(self, input, target):
        logp = jax.nn.log_softmax(input, axis=-1)
        return ClassNLLCriterion(self.weights, self.size_average).forward(
            logp, target)


class ClassSimplexCriterion(Criterion):
    """MSE against vertices of an (nClasses-1)-simplex embedding
    (reference nn/ClassSimplexCriterion.scala)."""

    def __init__(self, n_classes: int, size_average: bool = True):
        super().__init__(size_average)
        self.n_classes = n_classes
        self._simplex = self._build_simplex(n_classes)

    @staticmethod
    def _build_simplex(n: int) -> jnp.ndarray:
        # n unit-norm vertices of a regular (n-1)-simplex in R^n: center the
        # standard basis and rescale. Pairwise angles are all equal, which is
        # the property the reference's recurrence guarantees.
        import numpy as np
        v = np.eye(n) - 1.0 / n
        v /= np.linalg.norm(v[0])
        return jnp.asarray(v, jnp.float32)

    def forward(self, input, target):
        goal = jnp.take(self._simplex.astype(input.dtype),
                        target.astype(jnp.int32), axis=0)
        return self._reduce(jnp.square(input - goal))


class DistKLDivCriterion(Criterion):
    """KL(target || input) with input = log-probs
    (reference nn/DistKLDivCriterion.scala)."""

    def forward(self, input, target):
        per = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-12)) - input), 0.0)
        if self.size_average:
            # reference divides by the element count, not the batch size
            return jnp.sum(per) / input.size
        return jnp.sum(per)


class CosineEmbeddingCriterion(Criterion):
    """Table input ((x1, x2), y in {1,-1})
    (reference nn/CosineEmbeddingCriterion.scala, 195 LoC)."""

    def __init__(self, margin: float = 0.0, size_average: bool = True):
        super().__init__(size_average)
        self.margin = margin

    def forward(self, input, target):
        x1, x2 = input
        cos = jnp.sum(x1 * x2, axis=-1) / jnp.maximum(
            jnp.linalg.norm(x1, axis=-1) * jnp.linalg.norm(x2, axis=-1), 1e-12)
        pos = 1.0 - cos
        neg = jnp.maximum(0.0, cos - self.margin)
        per = jnp.where(target > 0, pos, neg)
        return self._reduce(per)


class HingeEmbeddingCriterion(Criterion):
    """x if y==1 else max(0, margin - x)
    (reference nn/HingeEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__(size_average)
        self.margin = margin

    def forward(self, input, target):
        per = jnp.where(target > 0, input,
                        jnp.maximum(0.0, self.margin - input))
        return self._reduce(per)


class L1HingeEmbeddingCriterion(Criterion):
    """Hinge embedding over L1 distance of a table (x1, x2)
    (reference nn/L1HingeEmbeddingCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__(size_average)
        self.margin = margin

    def forward(self, input, target):
        x1, x2 = input
        d = jnp.sum(jnp.abs(x1 - x2), axis=-1)
        per = jnp.where(target > 0, d, jnp.maximum(0.0, self.margin - d))
        return self._reduce(per)


class MarginCriterion(Criterion):
    """Hinge loss max(0, margin - y*x) (reference nn/MarginCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__(size_average)
        self.margin = margin

    def forward(self, input, target):
        return self._reduce(jnp.maximum(0.0, self.margin - target * input))


class MarginRankingCriterion(Criterion):
    """max(0, -y*(x1-x2) + margin) over table (x1, x2)
    (reference nn/MarginRankingCriterion.scala)."""

    def __init__(self, margin: float = 1.0, size_average: bool = True):
        super().__init__(size_average)
        self.margin = margin

    def forward(self, input, target):
        x1, x2 = input
        return self._reduce(jnp.maximum(0.0, -target * (x1 - x2) + self.margin))


class MultiCriterion(Criterion):
    """Weighted sum of criterions on the same (input, target)
    (reference nn/MultiCriterion.scala)."""

    def __init__(self):
        super().__init__()
        self._items: list[tuple[Criterion, float]] = []

    def add(self, criterion: Criterion, weight: float = 1.0) -> "MultiCriterion":
        self._items.append((criterion, weight))
        return self

    def forward(self, input, target):
        return sum(w * c.forward(input, target) for c, w in self._items)


class ParallelCriterion(Criterion):
    """Weighted sum of criterions over zipped table inputs/targets
    (reference nn/ParallelCriterion.scala). repeat_target broadcasts one
    target to every branch."""

    def __init__(self, repeat_target: bool = False):
        super().__init__()
        self.repeat_target = repeat_target
        self._items: list[tuple[Criterion, float]] = []

    def add(self, criterion: Criterion, weight: float = 1.0) -> "ParallelCriterion":
        self._items.append((criterion, weight))
        return self

    def forward(self, input, target):
        total = 0.0
        for i, (c, w) in enumerate(self._items):
            t = target if self.repeat_target else target[i]
            total = total + w * c.forward(input[i], t)
        return total


class MultiLabelMarginCriterion(Criterion):
    """Multi-class multi-label margin loss
    (reference nn/MultiLabelMarginCriterion.scala, 206 LoC). Target rows list
    label indices (0-based), padded with -1 (reference pads with 0 in 1-based)."""

    def forward(self, input, target):
        b, c = input.shape
        t = target.astype(jnp.int32)
        is_label = t >= 0
        t_safe = jnp.maximum(t, 0)
        tgt_scores = jnp.take_along_axis(input, t_safe, axis=1)  # (B, L)
        # mask of classes that are targets: (B, C). Additive scatter — a
        # plain set() would let padded rows (t_safe=0) overwrite index 0.
        tgt_mask = jnp.zeros((b, c), jnp.int32).at[
            jnp.arange(b)[:, None], t_safe].add(is_label.astype(jnp.int32)) > 0
        # hinge for every (target y, non-target i): max(0, 1 - (x[y] - x[i]))
        margins = 1.0 - (tgt_scores[:, :, None] - input[:, None, :])  # (B,L,C)
        valid = is_label[:, :, None] & ~tgt_mask[:, None, :]
        per = jnp.sum(jnp.where(valid, jnp.maximum(margins, 0.0), 0.0),
                      axis=(1, 2)) / c
        return self._reduce(per)


class MultiLabelSoftMarginCriterion(Criterion):
    """Sigmoid + BCE per class (reference nn/MultiLabelSoftMarginCriterion.scala)."""

    def __init__(self, weights: Optional[jnp.ndarray] = None,
                 size_average: bool = True):
        super().__init__(size_average)
        self.weights = weights

    def forward(self, input, target):
        # numerically-stable log-sigmoid formulation
        per = -(target * jax.nn.log_sigmoid(input)
                + (1.0 - target) * jax.nn.log_sigmoid(-input))
        if self.weights is not None:
            per = per * self.weights.astype(per.dtype)
        per = jnp.mean(per, axis=-1)
        return self._reduce(per)


class MultiMarginCriterion(Criterion):
    """Multi-class margin loss (reference nn/MultiMarginCriterion.scala)."""

    def __init__(self, p: int = 1, weights: Optional[jnp.ndarray] = None,
                 margin: float = 1.0, size_average: bool = True):
        super().__init__(size_average)
        assert p in (1, 2)
        self.p, self.weights, self.margin = p, weights, margin

    def forward(self, input, target):
        b, c = input.shape
        t = target.astype(jnp.int32)
        x_y = jnp.take_along_axis(input, t[:, None], axis=1)  # (B,1)
        h = jnp.maximum(0.0, self.margin - (x_y - input))  # (B,C)
        if self.p == 2:
            h = jnp.square(h)
        if self.weights is not None:
            h = h * jnp.take(self.weights.astype(h.dtype), t)[:, None]
        not_y = jnp.arange(c)[None, :] != t[:, None]
        per = jnp.sum(jnp.where(not_y, h, 0.0), axis=1) / c
        return self._reduce(per)


class SmoothL1Criterion(Criterion):
    """Huber-style smooth L1 (reference nn/SmoothL1Criterion.scala)."""

    def forward(self, input, target):
        d = jnp.abs(input - target)
        per = jnp.where(d < 1.0, 0.5 * jnp.square(d), d - 0.5)
        return self._reduce(per)


class SoftMarginCriterion(Criterion):
    """log(1 + exp(-y*x)) (reference nn/SoftMarginCriterion.scala)."""

    def forward(self, input, target):
        return self._reduce(jax.nn.softplus(-target * input))


class TimeDistributedCriterion(Criterion):
    """Apply a per-sample criterion across a time dimension: input
    (B, T, ...) + target (B, T, ...) are flattened to (B*T, ...) and fed to
    ``base``. The sequence analog the LM/seq2seq paths need (per-token NLL
    -> perplexity); the reference's Recurrent models instead emit one
    prediction per window."""

    def __init__(self, base: Criterion):
        super().__init__()
        self.base = base

    def forward(self, input, target):
        b, t = input.shape[0], input.shape[1]
        inp = input.reshape((b * t,) + input.shape[2:])
        tgt = target.reshape((b * t,) + target.shape[2:])
        return self.base.forward(inp, tgt)


class L1Cost(Criterion):
    """sum |x| of the input, target ignored (reference nn/L1Cost.scala)."""

    def forward(self, input, target=None):
        del target
        return jnp.sum(jnp.abs(input))


class L1Penalty(Criterion):
    """L1 activation penalty (reference nn/L1Penalty.scala exists as a module
    adding a sparsity penalty to the loss; here it is expressed directly as a
    criterion term to add via MultiCriterion)."""

    def __init__(self, l1weight: float = 1.0):
        super().__init__()
        self.l1weight = l1weight

    def forward(self, input, target=None):
        del target
        return self.l1weight * jnp.sum(jnp.abs(input))


class MultiTokenNLLCriterion(Criterion):
    """The loss of a language model with one multi-token prediction
    module (DeepSeek-V3's MTP, arXiv:2412.19437 section 2.2): ``CE_main +
    weight * CE_mtp``. Input ``{"main": (B, T, C), "mtp": (B, T, C)}``
    log-probs; target (B, T), the next token of every position. The main
    term is over every position; the MTP module's position ``t`` predicts
    the token after next, ``target[:, t + 1]``, so its term is over
    positions 0..T-2 (its last row is ignored). Each term is a mean over
    its positions."""

    def __init__(self, weight: float):
        super().__init__()
        self.weight = weight

    @staticmethod
    def _nll(logp, target):
        t = target.astype(jnp.int32)[..., None]
        return -jnp.mean(jnp.take_along_axis(logp, t, axis=-1))

    def forward(self, input, target):
        return (self._nll(input["main"], target) + self.weight
                * self._nll(input["mtp"][:, :-1], target[:, 1:]))
