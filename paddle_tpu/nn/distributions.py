"""Probability distributions (``fluid.layers.distributions`` parity).

Reference: ``python/paddle/fluid/layers/distributions.py:28-603`` —
``Distribution`` ABC plus ``Uniform`` (:113), ``Normal`` (:246),
``Categorical`` (:401) and ``MultivariateNormalDiag`` (:494), each exposing
``sample`` / ``entropy`` / ``log_prob`` / ``kl_divergence``.

TPU-native design notes
-----------------------
* Everything is pure ``jnp`` on broadcastable arrays — every method traces
  under ``jax.jit`` and ``vmap`` with static shapes.
* ``sample`` takes an explicit ``jax.random`` key (functional PRNG) instead
  of the reference's stateful ``seed=`` int; a ``seed`` kwarg is still
  accepted for API familiarity and folds into a key.
* The reference builds graph ops (``uniform_random_batch_size_like`` …) to
  handle unknown batch sizes; under JAX shapes are static at trace time so
  the two reference code paths collapse into one.
* Beyond the reference, ``Categorical`` gains ``sample``/``log_prob`` and
  ``MultivariateNormalDiag`` gains ``sample``/``log_prob`` (the reference
  leaves them unimplemented); shapes/semantics follow the same conventions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "Distribution", "Uniform", "Normal", "Categorical",
    "MultivariateNormalDiag", "kl_divergence",
]

_LOG_2PI = math.log(2.0 * math.pi)

# eager-convenience PRNG stream for sample() calls that pass neither key nor
# seed: fresh draw per call, like the reference's seed=0 ("use a fresh engine
# seed", gaussian_random_op.cc semantics). Under jit, pass `key` explicitly —
# the counter advances at trace time only, so the implicit draw would be
# BAKED into the compiled function; _key refuses that case loudly.
_default_stream = iter(range(1 << 62))


def _tracing() -> bool:
    """Inside any jax trace (jit/grad/vmap/scan)?"""
    return not jax.core.trace_ctx.is_top_level()


def _key(key, seed):
    if key is not None:
        return key
    if seed is not None:
        return jax.random.PRNGKey(seed)
    if _tracing():
        raise ValueError(
            "Distribution.sample() called with neither key= nor seed= "
            "inside a jax trace (jit/grad/vmap/scan): the implicit fresh "
            "draw happens at TRACE time, so the compiled function would "
            "silently replay ONE fixed sample forever. Pass key= (split "
            "it per step) for independent draws, or seed= to make the "
            "fixed draw explicit.")
    return jax.random.PRNGKey(next(_default_stream))


class Distribution:
    """Abstract base class for probability distributions
    (reference ``distributions.py:28``)."""

    def sample(self, shape=(), *, key=None, seed=None):
        raise NotImplementedError

    def entropy(self):
        raise NotImplementedError

    def log_prob(self, value):
        raise NotImplementedError

    def kl_divergence(self, other):
        raise NotImplementedError

    def prob(self, value):
        return jnp.exp(self.log_prob(value))


class Uniform(Distribution):
    """Uniform distribution on ``[low, high)``
    (reference ``distributions.py:113``)."""

    def __init__(self, low, high):
        self.low = jnp.asarray(low, dtype=jnp.result_type(float))
        self.high = jnp.asarray(high, dtype=self.low.dtype)

    @property
    def batch_shape(self):
        return jnp.broadcast_shapes(self.low.shape, self.high.shape)

    def sample(self, shape=(), *, key=None, seed=None):
        shape = tuple(shape) + self.batch_shape
        u = jax.random.uniform(_key(key, seed), shape, dtype=self.low.dtype)
        return self.low + u * (self.high - self.low)

    def log_prob(self, value):
        value = jnp.asarray(value, dtype=self.low.dtype)
        # log(in_support ? 1 : 0) - log(high-low): -inf outside the support
        # (the reference's lb*ub mask, distributions.py:221-233, but with an
        # inclusive lower bound — sample() can return exactly `low`)
        inside = (self.low <= value) & (value < self.high)
        return jnp.where(inside, 0.0, -jnp.inf) - jnp.log(self.high - self.low)

    def entropy(self):
        return jnp.broadcast_to(jnp.log(self.high - self.low),
                                self.batch_shape)

    def kl_divergence(self, other):
        if not isinstance(other, Uniform):
            raise TypeError("kl_divergence expects another Uniform")
        # KL(U[a,b] || U[c,d]) = log((d-c)/(b-a)) when [a,b] ⊆ [c,d], ∞ else
        contained = (other.low <= self.low) & (self.high <= other.high)
        kl = (jnp.log(other.high - other.low)
              - jnp.log(self.high - self.low))
        return jnp.where(contained, kl, jnp.inf)


class Normal(Distribution):
    """Normal(loc, scale) (reference ``distributions.py:246``)."""

    def __init__(self, loc, scale):
        self.loc = jnp.asarray(loc, dtype=jnp.result_type(float))
        self.scale = jnp.asarray(scale, dtype=self.loc.dtype)

    @property
    def batch_shape(self):
        return jnp.broadcast_shapes(self.loc.shape, self.scale.shape)

    def sample(self, shape=(), *, key=None, seed=None):
        shape = tuple(shape) + self.batch_shape
        eps = jax.random.normal(_key(key, seed), shape, dtype=self.loc.dtype)
        return self.loc + eps * self.scale

    def entropy(self):
        # 0.5 + 0.5*log(2π) + log(σ)   (reference distributions.py:356-366)
        return jnp.broadcast_to(0.5 + 0.5 * _LOG_2PI + jnp.log(self.scale),
                                self.batch_shape)

    def log_prob(self, value):
        value = jnp.asarray(value, dtype=self.loc.dtype)
        var = self.scale * self.scale
        return (-((value - self.loc) ** 2) / (2.0 * var)
                - jnp.log(self.scale) - 0.5 * _LOG_2PI)

    def kl_divergence(self, other):
        if not isinstance(other, Normal):
            raise TypeError("kl_divergence expects another Normal")
        # 0.5*(σ²ratio + t1² - 1 - log σ²ratio)  (reference :384-398)
        var_ratio = (self.scale / other.scale) ** 2
        t1 = ((self.loc - other.loc) / other.scale) ** 2
        return 0.5 * (var_ratio + t1 - 1.0 - jnp.log(var_ratio))


class Categorical(Distribution):
    """Categorical over the trailing axis of ``logits``
    (reference ``distributions.py:401``)."""

    def __init__(self, logits):
        self.logits = jnp.asarray(logits, dtype=jnp.result_type(float))

    @property
    def _log_normalized(self):
        logits = self.logits - jnp.max(self.logits, axis=-1, keepdims=True)
        return logits - jnp.log(
            jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))

    @property
    def probs(self):
        return jax.nn.softmax(self.logits, axis=-1)

    def sample(self, shape=(), *, key=None, seed=None):
        # beyond-reference: fluid's Categorical has no sample (:401)
        return jax.random.categorical(_key(key, seed), self.logits,
                                      shape=tuple(shape) + self.logits.shape[:-1])

    def log_prob(self, value):
        value = jnp.asarray(value, dtype=jnp.int32)
        return jnp.take_along_axis(self._log_normalized, value[..., None],
                                   axis=-1)[..., 0]

    def entropy(self):
        # -Σ p·(logits - log z), computed max-shifted (reference :477-490).
        # p·log p is defined by continuity as 0 at p=0 — a saturated policy
        # has logp → -inf where exp(logp) → 0, and 0·(-inf) would be NaN.
        # Double-where: the -inf operand must be masked BEFORE the multiply,
        # or the 0·(-inf)=NaN inside the untaken branch poisons gradients
        # (action-masked policies carry -inf logits routinely).
        logp = self._log_normalized
        dead = jnp.isneginf(logp)
        plogp = jnp.exp(logp) * jnp.where(dead, 0.0, logp)
        return -jnp.sum(plogp, axis=-1, keepdims=True)

    def kl_divergence(self, other):
        if not isinstance(other, Categorical):
            raise TypeError("kl_divergence expects another Categorical")
        logp, logq = self._log_normalized, other._log_normalized
        # p=0 terms contribute 0 by continuity (q=0 with p>0 stays +inf);
        # double-where so -inf never meets the multiply (NaN-free grads)
        dead = jnp.isneginf(logp)
        term = jnp.exp(logp) * jnp.where(dead, 0.0, logp - logq)
        return jnp.sum(term, axis=-1, keepdims=True)


class MultivariateNormalDiag(Distribution):
    """Multivariate normal with diagonal covariance
    (reference ``distributions.py:494``).

    ``scale`` is the diagonal covariance matrix, as in the reference (a
    ``[k, k]`` matrix whose off-diagonal entries are ignored — the reference
    masks them with ``_det``/``_inv`` built from ``diag(ones)``).
    """

    def __init__(self, loc, scale):
        self.loc = jnp.asarray(loc, dtype=jnp.result_type(float))
        self.scale = jnp.asarray(scale, dtype=self.loc.dtype)
        if self.scale.ndim < 2 or self.scale.shape[-1] != self.scale.shape[-2]:
            raise ValueError("scale must be a [k, k] diagonal covariance "
                             f"matrix, got {self.scale.shape}")

    @property
    def _diag(self):
        return jnp.diagonal(self.scale, axis1=-2, axis2=-1)

    def _log_det(self):
        return jnp.sum(jnp.log(self._diag), axis=-1)

    def sample(self, shape=(), *, key=None, seed=None):
        # beyond-reference; covariance diag = σ² ⇒ std = sqrt(diag)
        shape = tuple(shape) + self.loc.shape
        eps = jax.random.normal(_key(key, seed), shape, dtype=self.loc.dtype)
        return self.loc + eps * jnp.sqrt(self._diag)

    def entropy(self):
        k = self.loc.shape[-1]
        return 0.5 * (k * (1.0 + _LOG_2PI) + self._log_det())

    def log_prob(self, value):
        value = jnp.asarray(value, dtype=self.loc.dtype)
        k = self.loc.shape[-1]
        diff = value - self.loc
        maha = jnp.sum(diff * diff / self._diag, axis=-1)
        return -0.5 * (k * _LOG_2PI + self._log_det() + maha)

    def kl_divergence(self, other):
        if not isinstance(other, MultivariateNormalDiag):
            raise TypeError("kl_divergence expects another "
                            "MultivariateNormalDiag")
        # 0.5*(tr(Σq⁻¹Σp) + Δᵀ Σq⁻¹ Δ - k + ln|Σq|/|Σp|)  (reference :575-595)
        dp, dq = self._diag, other._diag
        diff = other.loc - self.loc
        tr = jnp.sum(dp / dq, axis=-1)
        maha = jnp.sum(diff * diff / dq, axis=-1)
        k = self.loc.shape[-1]
        return 0.5 * (tr + maha - k + self._log_det_other(other))

    def _log_det_other(self, other):
        return other._log_det() - self._log_det()


def kl_divergence(p: Distribution, q: Distribution):
    """Functional form: ``kl_divergence(p, q) == p.kl_divergence(q)``."""
    return p.kl_divergence(q)
