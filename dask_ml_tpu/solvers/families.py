"""GLM families — twin of ``dask_glm/families.py`` (``Logistic``, ``Normal``,
``Poisson``: ``pointwise_loss`` / ``pointwise_gradient`` / hessian weights).

TPU-first twist: families only define the masked scalar loss; gradients are
``jax.grad`` of it (no hand-derived gradient code to keep in sync), and the
Newton solver asks for per-sample hessian weights only.

The loss has two parts, and ``loss`` is their composition: the linear
predictor ``X @ weights + intercept`` (the only part that reads the design
matrix; the intercept is the parameter vector's last entry when it is one
longer than ``X`` is wide, ``Family.split``) and the
masked pointwise loss of it, ``pointwise_loss(eta, y, mask)``, which is
what a family defines.  A line search that has ``eta = X @ x`` and
``u = X @ p`` needs only the second part to evaluate any step along ``p``
(``solvers/lbfgs_core.py :: LinearObjective``).  A family that overrides
``loss`` alone still works everywhere: ``admm`` and ``lbfgs`` then search
on the black-box objective, as they do for a matrix of parameters
(``algorithms._lbfgs_objective``).
"""

from __future__ import annotations

from functools import lru_cache

import jax.numpy as jnp
from jax import lax


class Family:
    #: parameters per feature: the flat beta reshapes to (features, K)
    params_per_feature = 1

    @classmethod
    def split(cls, beta, X):
        """``(weights, intercept)`` of a flat parameter vector, told apart
        by its length against ``X``'s width ``d``: ``d * K`` numbers are
        weights alone (``intercept`` is None; a caller who wants a
        constant term then has a column of ones in ``X``), ``(d + 1) * K``
        carry the intercept LAST, where an appended column of ones would
        have put it.  Weights are ``(d,)``, or ``(d, K)`` when K > 1; the
        intercept a scalar, or ``(K,)``."""
        k, d = cls.params_per_feature, X.shape[1]
        if beta.shape[0] not in (d * k, (d + 1) * k):
            raise ValueError(
                f"{beta.shape[0]} parameters for a table {d} wide: this "
                f"family needs {d * k}, or {(d + 1) * k} with an intercept")
        b = beta.reshape(-1, k) if k > 1 else beta
        return (b, None) if beta.shape[0] == d * k else (b[:-1], b[-1])

    @classmethod
    def linear_predictor(cls, beta, X):
        """``eta = X @ weights (+ intercept)``; (n, K) when K > 1.  The
        intercept rides beside the weights as a scalar added to the
        product: no column of ones is read, or made."""
        return cls.linear_predictors(X, beta)[0]

    @classmethod
    def linear_predictors(cls, X, *betas):
        """``linear_predictor`` of each of a few parameter vectors in ONE
        read of ``X`` (:meth:`products`), as a tuple."""
        weights, intercepts = zip(*(cls.split(b, X) for b in betas))
        return tuple(
            eta if b0 is None else eta + b0
            for eta, b0 in zip(cls.products(X, *weights), intercepts))

    @classmethod
    def products(cls, X, *weights):
        """``X @ w`` for each of a few weight arrays (as :meth:`split`
        shapes them) in ONE read of ``X``, as a tuple: separate products
        would each read ``X``.  A matrix of weights (K > 1) is a matrix
        product already, so several are one product with their columns
        side by side.  A vector (K == 1) is a multiply-and-sum in the
        accumulation dtype, which is what XLA makes of a matrix-vector
        product, so several are one reduction with several results: a dot
        with their columns side by side would go to the matrix unit at
        the backend's default precision instead."""
        if len(weights) == 1:
            return (X @ weights[0],)
        if cls.params_per_feature > 1:
            return tuple(jnp.split(
                X @ jnp.concatenate(weights, axis=1), len(weights), axis=1))
        terms = tuple(X * w for w in weights)  # (n, d)
        return lax.reduce(
            terms, tuple(jnp.zeros((), t.dtype) for t in terms),
            lambda acc, new: tuple(a + b for a, b in zip(acc, new)), (1,))

    @staticmethod
    def pointwise_loss(eta, y, mask):  # masked total loss of a predictor
        raise NotImplementedError

    @classmethod
    def loss(cls, beta, X, y, mask):  # total masked negative log-likelihood
        return cls.pointwise_loss(cls.linear_predictor(beta, X), y, mask)

    @staticmethod
    def hessian_weights(eta):  # per-sample d²loss/deta² at linear predictor eta
        raise NotImplementedError

    @staticmethod
    def predict(eta):  # mean response from linear predictor
        raise NotImplementedError


class Logistic(Family):
    """y ∈ {0,1}; loss = Σ log(1+exp(Xβ)) − y·Xβ."""

    @staticmethod
    def pointwise_loss(eta, y, mask):
        # log(1+e^eta) computed stably
        return jnp.sum(mask * (jnp.logaddexp(0.0, eta) - y * eta))

    @staticmethod
    def hessian_weights(eta):
        p = 1.0 / (1.0 + jnp.exp(-eta))
        return p * (1.0 - p)

    @staticmethod
    def predict(eta):
        return 1.0 / (1.0 + jnp.exp(-eta))


class Normal(Family):
    """Gaussian: loss = ½ Σ (y − Xβ)²."""

    @staticmethod
    def pointwise_loss(eta, y, mask):
        return 0.5 * jnp.sum(mask * (y - eta) ** 2)

    @staticmethod
    def hessian_weights(eta):
        return jnp.ones_like(eta)

    @staticmethod
    def predict(eta):
        return eta


@lru_cache(maxsize=None)
def multinomial(n_classes: int) -> type[Family]:
    """True softmax (multinomial) logistic family for K classes.

    The reference's dask_glm is binary-only (``families.py :: Logistic``);
    this closes the gap the reference punts on.  The flat parameter vector
    reshapes to (features, K) inside the loss (``params_per_feature`` tells
    the solvers to size beta accordingly), ``y`` holds integer class
    indices, and the picked-class logit is an inner product with a one-hot
    row — a gather (``take_along_axis``) is ~10x slower on XLA:TPU.

    Cached per K so the solver jit caches (keyed on the family as a static
    argument) are reused across fits.
    """

    class _Multinomial(Family):
        params_per_feature = n_classes

        @staticmethod
        def pointwise_loss(eta, y, mask):  # eta (n, K)
            import jax

            lse = jax.nn.logsumexp(eta, axis=1)
            onehot = jax.nn.one_hot(
                y.astype(jnp.int32), n_classes, dtype=eta.dtype
            )
            picked = jnp.sum(eta * onehot, axis=1)
            return jnp.sum(mask * (lse - picked))

        @staticmethod
        def predict(eta):
            import jax

            return jax.nn.softmax(eta, axis=-1)

    _Multinomial.__name__ = f"Multinomial{n_classes}"
    return _Multinomial


class Poisson(Family):
    """Counts: loss = Σ exp(Xβ) − y·Xβ."""

    @staticmethod
    def pointwise_loss(eta, y, mask):
        return jnp.sum(mask * (jnp.exp(eta) - y * eta))

    @staticmethod
    def hessian_weights(eta):
        return jnp.exp(eta)

    @staticmethod
    def predict(eta):
        return jnp.exp(eta)


def lane_products(X, *weights):
    """``X @ w`` for each of a few weight VECTORS in float32, in the form
    for lanes that a caller puts under ``vmap``.  There ``X @ w`` of a
    batch of vectors is a matrix product, which the TPU's default
    precision feeds a bfloat16 copy of ``X``, made again inside every
    loop that holds it.  One vector is a product at ``HIGHEST``
    precision, so that its transpose (the gradient's ``X' r`` for every
    lane) is one too: as a multiply-and-sum over the rows it took four
    reads' worth of time on a v5e, as a product one.  Several vectors
    are one multiply-and-sum reduction with several results, as
    :meth:`Family.products` forms them."""
    if len(weights) > 1:
        return Family.products(X, *weights)
    return (jnp.dot(X, weights[0], precision=lax.Precision.HIGHEST),)
