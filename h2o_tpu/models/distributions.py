"""Distribution families for boosting and GLM-style models.

Reference: hex/DistributionFactory.java + hex/Distribution.java subclasses
(h2o-core/src/main/java/hex/) — each family defines the link, the per-row
gradient ("residual" in H2O's GBM formulation, ComputePredAndRes
gbm/GBM.java:464-528), the Newton denominator used by GammaPass leaf fitting,
and the deviance used for metrics/early-stopping.

All functions are elementwise jnp — they fuse into the surrounding XLA
programs (scoring, histogram stats prep).
"""

from __future__ import annotations

import jax.numpy as jnp

EPS = 1e-10


def weighted_mean(y, w):
    """sum(w * y) / max(sum(w), EPS) over the rows, each sum computed
    where the rows live (``hsum_rows``)."""
    from h2o_tpu.core.cloud import hsum_rows
    return hsum_rows(w * y, "f0.sums") / jnp.maximum(
        hsum_rows(w, "f0.sums"), EPS)


class Distribution:
    """gradient/hessian are with respect to f (the link-scale prediction),
    following the classic gradient-boosting formulation the reference uses:
    residual r = -dL/df, newton denominator h = d2L/df2."""

    name = "base"
    link = "identity"

    def init_f0(self, y, w):
        """Initial constant prediction on the link scale."""
        m = weighted_mean(y, w)
        return self.link_fn(m)

    def link_fn(self, mu):
        return mu

    def link_inv(self, f):
        return f

    def gradient(self, y, f):
        """Negative gradient (the 'residual' GBM fits trees to)."""
        raise NotImplementedError

    def hessian(self, y, f):
        """Newton denominator for leaf values (GammaPass)."""
        return jnp.ones_like(f)

    def deviance(self, w, y, f):
        """Per-row deviance contribution (link-scale f)."""
        raise NotImplementedError


class Gaussian(Distribution):
    name = "gaussian"

    def gradient(self, y, f):
        return y - f

    def deviance(self, w, y, f):
        return w * (y - f) ** 2


class Bernoulli(Distribution):
    name = "bernoulli"
    link = "logit"

    def init_f0(self, y, w):
        p = jnp.clip(weighted_mean(y, w),
                     EPS, 1 - EPS)
        return jnp.log(p / (1 - p))

    def link_fn(self, mu):
        mu = jnp.clip(mu, EPS, 1 - EPS)
        return jnp.log(mu / (1 - mu))

    def link_inv(self, f):
        return 1.0 / (1.0 + jnp.exp(-f))

    def gradient(self, y, f):
        return y - self.link_inv(f)

    def hessian(self, y, f):
        p = self.link_inv(f)
        return p * (1.0 - p)

    def deviance(self, w, y, f):
        p = jnp.clip(self.link_inv(f), EPS, 1 - EPS)
        return -2.0 * w * (y * jnp.log(p) + (1 - y) * jnp.log(1 - p))


class Multinomial(Distribution):
    """Handled specially by builders (K trees / softmax); per-class pieces
    reuse bernoulli-style gradients on one-vs-all with softmax probs."""

    name = "multinomial"
    link = "log"


class Poisson(Distribution):
    name = "poisson"
    link = "log"

    def init_f0(self, y, w):
        return jnp.log(jnp.maximum(
            weighted_mean(y, w), EPS))

    def link_fn(self, mu):
        return jnp.log(jnp.maximum(mu, EPS))

    def link_inv(self, f):
        return jnp.exp(f)

    def gradient(self, y, f):
        return y - jnp.exp(f)

    def hessian(self, y, f):
        return jnp.exp(f)

    def deviance(self, w, y, f):
        mu = jnp.maximum(jnp.exp(f), EPS)
        ylogy = jnp.where(y > 0, y * jnp.log(jnp.maximum(y, EPS) / mu), 0.0)
        return 2.0 * w * (ylogy - (y - mu))


class Gamma(Distribution):
    name = "gamma"
    link = "log"

    def init_f0(self, y, w):
        return jnp.log(jnp.maximum(
            weighted_mean(y, w), EPS))

    def link_fn(self, mu):
        return jnp.log(jnp.maximum(mu, EPS))

    def link_inv(self, f):
        return jnp.exp(f)

    def gradient(self, y, f):
        return y * jnp.exp(-f) - 1.0

    def hessian(self, y, f):
        return y * jnp.exp(-f)

    def deviance(self, w, y, f):
        mu = jnp.maximum(jnp.exp(f), EPS)
        ys = jnp.maximum(y, EPS)
        return 2.0 * w * (-jnp.log(ys / mu) + (ys - mu) / mu)


class Tweedie(Distribution):
    name = "tweedie"
    link = "log"

    def __init__(self, power: float = 1.5):
        assert 1.0 < power < 2.0, "tweedie variance power in (1,2)"
        self.p = power

    def init_f0(self, y, w):
        return jnp.log(jnp.maximum(
            weighted_mean(y, w), EPS))

    def link_fn(self, mu):
        return jnp.log(jnp.maximum(mu, EPS))

    def link_inv(self, f):
        return jnp.exp(f)

    def gradient(self, y, f):
        p = self.p
        return y * jnp.exp(f * (1 - p)) - jnp.exp(f * (2 - p))

    def hessian(self, y, f):
        p = self.p
        return ((p - 1) * y * jnp.exp(f * (1 - p)) +
                (2 - p) * jnp.exp(f * (2 - p)))

    def deviance(self, w, y, f):
        p = self.p
        mu = jnp.maximum(jnp.exp(f), EPS)
        return 2.0 * w * (
            jnp.maximum(y, 0.0) ** (2 - p) / ((1 - p) * (2 - p))
            - y * mu ** (1 - p) / (1 - p) + mu ** (2 - p) / (2 - p))


class Laplace(Distribution):
    name = "laplace"

    def gradient(self, y, f):
        return jnp.sign(y - f)

    def deviance(self, w, y, f):
        return w * jnp.abs(y - f)


class QuantileDist(Distribution):
    name = "quantile"

    def __init__(self, alpha: float = 0.5):
        self.alpha = alpha

    def gradient(self, y, f):
        return jnp.where(y > f, self.alpha, self.alpha - 1.0)

    def deviance(self, w, y, f):
        d = y - f
        return w * jnp.where(d > 0, self.alpha * d, (self.alpha - 1) * d)


class Huber(Distribution):
    name = "huber"

    def __init__(self, delta: float = 1.0):
        self.delta = delta

    def gradient(self, y, f):
        d = y - f
        return jnp.clip(d, -self.delta, self.delta)

    def deviance(self, w, y, f):
        d = jnp.abs(y - f)
        return w * jnp.where(d <= self.delta, 0.5 * d * d,
                             self.delta * (d - 0.5 * self.delta))


_FAMILIES = {
    "gaussian": Gaussian, "bernoulli": Bernoulli, "binomial": Bernoulli,
    "multinomial": Multinomial, "poisson": Poisson, "gamma": Gamma,
    "laplace": Laplace, "huber": Huber,
}


def get_distribution(name: str, **kw) -> Distribution:
    name = name.lower()
    if name == "auto":
        raise ValueError("resolve AUTO before calling get_distribution")
    if name == "tweedie":
        return Tweedie(kw.get("tweedie_power", 1.5))
    if name == "quantile":
        return QuantileDist(kw.get("quantile_alpha", 0.5))
    if name == "huber":
        return Huber(kw.get("huber_alpha", 1.0))
    return _FAMILIES[name]()
