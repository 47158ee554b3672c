"""JAX-native batched GP/BO engine — the hardware-speed suggest path.

The numpy/scipy :class:`~repro.core.optimizers.gaussian_process.GP` is the
*reference* backend: it refits from scratch (O(n³) Cholesky + 3×L-BFGS-B)
on every ``ask``, which is fine for a notebook but not for the paper's
*inline* agent loop (§2 — the optimizer rides next to the system it tunes).
This module is the production backend:

  * **Rank-1 incremental Cholesky** — ``observe`` appends one row to the
    factor in O(n²) (a masked triangular solve) instead of refactoring the
    whole kernel matrix.  Duplicate encodings never re-enter the factor: the
    kernel matrix depends only on X, so a collapsed categorical just folds
    its (best) y into the existing row.
  * **Padded-shape history buffers** — X/y/L live in fixed ``max_n`` buffers
    (power-of-two buckets, floor :data:`MIN_BUCKET`) with an explicit row
    mask, so XLA recompiles only when history crosses a bucket boundary,
    never per-observation.  Padded rows are identity rows of the factor and
    zeros everywhere else, which keeps every solve exact.
  * **Device-resident state** — X/y/mask/θ/L stay on device between calls;
    a ``tell`` is ONE fused dispatch (append row + rank-1 factor update) and
    an ``ask`` uploads only the fresh candidate pool.  y-normalization, the
    incumbent best and the live count n are derived *inside* the jitted
    functions from the resident buffers, so no per-ask scalar uploads.
  * **Jitted multi-start hyperparameter fit** — projected Adam on the masked
    marginal likelihood, ``vmap`` over restarts, one compiled ``lax.scan``;
    refits are amortized (every :attr:`JaxGP.refit_every` observations and at
    bucket growth) rather than per-ask.
  * **Fused acquisition sweep** — EI/UCB over the whole candidate pool (1280
    rows in the default :class:`~.bayesopt.BayesOpt` shape) is a single XLA
    call: ``lax.scan`` over candidate blocks of posterior + acquisition,
    argmax included.  Acquisition kind and β are compile-time constants.
  * **Mux-wide batched ask** — :class:`BatchedBayesOpt` stacks the resident
    state of N same-shaped sessions and issues every suggestion in ONE
    fused ``vmap``+``jit`` dispatch, so one agent-daemon poll prices all
    sessions with a single kernel launch's worth of overhead.  (Not
    ``pmap``: measured slower on the CPU backend — see
    :func:`_batched_suggest_fn`.)

Everything runs in float64 on the host CPU, under this module's
:func:`enable_x64` — Cholesky at jitter 1e-8 is not float32-safe, parity
with the numpy reference is a tested contract, not an aspiration, and on a
TPU host the default device is the chip, which belongs to the model the
process serves or trains.  Compiled functions are cached per
(kernel[, acq]) by ``lru_cache``; jit's own cache keys the rest on the
(d, n_bucket, pool) shapes — and the batched session axis is padded to a
power of two — so every shape family compiles O(1) programs, never one per
observation count or per ready-session count.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular
from jax.scipy.stats import norm as jnorm

from ... import compat

__all__ = ["JaxGP", "BatchedBayesOpt", "batched_ask", "bucket_of", "MIN_BUCKET"]

MIN_BUCKET = 16          # smallest history buffer (rows)
_JITTER = 1e-8           # matches the numpy reference's (noise + 1e-8) diagonal
_CHUNK = 256             # candidate rows per lax.scan block
_ADAM_STEPS = 60
_ADAM_LR = 0.08
# log-space hyper bounds (ls, sv, nv) — identical to the reference L-BFGS-B box
_THETA_LO = (-4.6, -4.6, -13.8)
_THETA_HI = (2.3, 4.6, 0.0)
_LS_STARTS = (0.1, 0.3, 1.0)


@contextlib.contextmanager
def enable_x64():
    """The engine's compute context: arrays built and programs run inside it
    are float64 and live on the host CPU (``compat.enable_x64`` plus the CPU
    as default device)."""
    with compat.enable_x64(), jax.default_device(jax.devices("cpu")[0]):
        yield


def bucket_of(n: int) -> int:
    """Smallest power-of-two buffer holding ``n`` rows (floor MIN_BUCKET)."""
    if n <= MIN_BUCKET:
        return MIN_BUCKET
    return 1 << (n - 1).bit_length()


# ------------------------------------------------------------------ kernels
def _sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1), 0.0)


def _rbf(a, b, ls):
    return jnp.exp(-0.5 * _sqdist(a, b) / (ls * ls))


def _matern32(a, b, ls):
    d = jnp.sqrt(_sqdist(a, b)) / ls
    s3 = math.sqrt(3.0)
    return (1.0 + s3 * d) * jnp.exp(-s3 * d)


def _matern52(a, b, ls):
    d = jnp.sqrt(_sqdist(a, b)) / ls
    s5 = math.sqrt(5.0)
    return (1.0 + s5 * d + 5.0 / 3.0 * d * d) * jnp.exp(-s5 * d)


_KERNELS = {"rbf": _rbf, "matern32": _matern32, "matern52": _matern52}


def _ystats(yd, mask):
    """(n, ymean, ystd, yn, best) from the resident padded buffers — the
    jitted twin of the numpy reference's normalization."""
    n = jnp.maximum(mask.sum(), 1.0)
    ymean = (yd * mask).sum() / n
    ystd = jnp.sqrt((((yd - ymean) * mask) ** 2).sum() / n) + 1e-12
    yn = (yd - ymean) / ystd * mask
    best = jnp.min(jnp.where(mask > 0, yd, jnp.inf))
    return n, ymean, ystd, yn, best


# ------------------------------------------------------- compiled primitives
@functools.lru_cache(maxsize=None)
def _compiled(kernel: str) -> Dict[str, Any]:
    """Jitted state-maintenance primitives for one kernel family.

    Shapes (d, max_n) key jit's own cache, so each (kernel, d, n_bucket)
    combination compiles exactly once per process.
    """
    kfn = _KERNELS[kernel]

    def full_chol(X, mask, theta):
        """Cholesky of the masked kernel matrix; padded rows are identity."""
        ls, sv, nv = theta
        m2 = mask[:, None] * mask[None, :]
        # real diagonal = sv·k(x,x) + nv + jitter (k(x,x)=1); padded diag = 1
        K = sv * kfn(X, X, ls) * m2
        K = K + jnp.diag(mask * (nv + _JITTER) + (1.0 - mask))
        return jnp.linalg.cholesky(K)

    def append(L, X, yd, mask, x_new, y_new, theta):
        """One fused tell: write row n into X/y/mask and extend the factor
        by the rank-1 row — an O(n²) masked triangular solve."""
        ls, sv, nv = theta
        n = mask.sum().astype(jnp.int32)
        k_vec = sv * kfn(X, x_new[None, :], ls)[:, 0] * mask
        l = solve_triangular(L, k_vec, lower=True)
        k_ss = sv + nv + _JITTER
        l_ss = jnp.sqrt(jnp.maximum(k_ss - l @ l, 1e-12))
        # mloslint: disable=MLOS005 -- integer index mask, dtype-neutral; this closure
        # is only ever traced via tell() paths that run under the engine's enable_x64.
        row = jnp.where(jnp.arange(L.shape[0]) < n, l, 0.0)
        row = row.at[n].set(l_ss)
        return (L.at[n].set(row), X.at[n].set(x_new), yd.at[n].set(y_new),
                mask.at[n].set(1.0))

    def set_y(yd, row, val):
        """Duplicate-encoding fold: K (and L) depend only on X, so only the
        observed value changes."""
        return yd.at[row].set(val)

    def _alpha(L, yn):
        z = solve_triangular(L, yn, lower=True)
        return solve_triangular(L.T, z, lower=False)

    def nll(theta_log, X, mask, yn, n):
        """Masked negative log marginal likelihood (padded rows contribute 0)."""
        L = full_chol(X, mask, jnp.exp(theta_log))
        alpha = _alpha(L, yn)
        logdet = jnp.sum(jnp.log(jnp.maximum(jnp.diagonal(L), 1e-300)))
        v = 0.5 * yn @ alpha + logdet + 0.5 * n * math.log(2 * math.pi)
        return jnp.where(jnp.isnan(v), 1e10, v)

    grad_nll = jax.grad(nll)
    with enable_x64():  # constants frozen by lru_cache must be f64 too
        t_lo, t_hi = jnp.array(_THETA_LO), jnp.array(_THETA_HI)

    def fit_hypers(X, mask, yd, theta0s):
        """Projected multi-start Adam on the NLL; vmap over restarts."""
        n, _, _, yn, _ = _ystats(yd, mask)

        def one(theta0):
            def step(carry, _):
                th, m_t, v_t, t = carry
                g = grad_nll(th, X, mask, yn, n)
                g = jnp.where(jnp.isnan(g), 0.0, g)
                m2 = 0.9 * m_t + 0.1 * g
                v2 = 0.999 * v_t + 0.001 * g * g
                t2 = t + 1.0
                mhat = m2 / (1.0 - 0.9 ** t2)
                vhat = v2 / (1.0 - 0.999 ** t2)
                th2 = th - _ADAM_LR * mhat / (jnp.sqrt(vhat) + 1e-8)
                th2 = jnp.clip(th2, t_lo, t_hi)
                return (th2, m2, v2, t2), None

            z = jnp.zeros_like(theta0)
            (th, _, _, _), _ = lax.scan(step, (theta0, z, z, 0.0), None,
                                        length=_ADAM_STEPS)
            return th, nll(th, X, mask, yn, n)

        ths, vals = jax.vmap(one)(theta0s)
        return jnp.exp(ths[jnp.argmin(vals)])

    return {
        "full_chol": jax.jit(full_chol),
        "append": jax.jit(append),
        "set_y": jax.jit(set_y),
        "fit_hypers": jax.jit(fit_hypers),
        "kfn": kfn,
        "alpha": _alpha,
    }


@functools.lru_cache(maxsize=None)
def _suggest_fns(kernel: str, acq_id: int, beta: float) -> Dict[str, Any]:
    """The fused pool sweep, specialized per (kernel, acquisition, β) —
    acquisition parameters are compile-time constants, so an ask uploads
    nothing but the candidate pool."""
    fns = _compiled(kernel)
    kfn, alpha_of = fns["kfn"], fns["alpha"]

    def suggest(L, X, mask, yd, theta, cand):
        """Posterior + acquisition + argmax over the pool, one XLA call.

        ``cand`` must be padded to a multiple of _CHUNK; ``lax.scan`` over
        the blocks bounds the (max_n × pool) working set.
        """
        ls, sv, nv = theta
        _, ymean, ystd, yn, best = _ystats(yd, mask)
        alpha = alpha_of(L, yn)
        blocks = cand.reshape(cand.shape[0] // _CHUNK, _CHUNK, cand.shape[1])

        def body(carry, cb):
            Ks = sv * kfn(X, cb, ls) * mask[:, None]
            mu = Ks.T @ alpha
            v = solve_triangular(L, Ks, lower=True)
            var = jnp.maximum(sv - (v * v).sum(0), 1e-12)
            mu_d = mu * ystd + ymean
            sd_d = jnp.sqrt(var) * ystd
            if acq_id == 1:  # lower-confidence bound for minimization
                s = -(mu_d - beta * sd_d)
            else:
                imp = best - mu_d
                z = imp / jnp.maximum(sd_d, 1e-12)
                ei = imp * jnorm.cdf(z) + sd_d * jnorm.pdf(z)
                s = jnp.where(sd_d > 1e-12, ei, 0.0)
            return carry, s

        _, scores = lax.scan(body, 0, blocks)
        scores = scores.reshape(-1)
        return jnp.argmax(scores), scores

    return {"jit": jax.jit(suggest), "raw": suggest}


@functools.lru_cache(maxsize=None)
def _batched_suggest_fn(kernel: str, acq_id: int, beta: float):
    """vmapped suggest over a session axis, session-stacking fused INTO the
    jitted program (args are a pytree of per-session resident tuples, so no
    host-side stack dispatches) and only the argmax indices materialized.

    Deliberately ``vmap``+``jit``, not ``pmap``: on the CPU backend the
    single-session solves already saturate the intra-op thread pool, and
    measured ``pmap`` replica overhead (with or without pre-sharded inputs)
    is several times *slower* than one fused vmap dispatch.  The batched win
    here is amortized dispatch, not extra FLOP parallelism.
    """
    raw = _suggest_fns(kernel, acq_id, beta)["raw"]

    def run(states, cands):
        stacked = [jnp.stack(col) for col in zip(*states)]
        idxs, _scores = jax.vmap(raw)(*stacked, cands)
        return idxs

    return jax.jit(run)


def _pad_pool(cand: np.ndarray) -> np.ndarray:
    """Pad the candidate pool to a _CHUNK multiple (duplicates of the last
    row — argmax returns the first occurrence, so padding can't win)."""
    c = len(cand)
    rem = -c % _CHUNK
    if rem:
        cand = np.concatenate([cand, np.repeat(cand[-1:], rem, axis=0)])
    return cand


# ------------------------------------------------------------------- engine
class JaxGP:
    """Incremental, bucket-padded GP surrogate for one optimizer.

    ``observe`` is one fused O(n²) device dispatch (rank-1 factor append;
    duplicate rows fold in place); ``suggest`` is one fused device call that
    uploads only the candidate pool.  Hyperparameters refit on a cadence
    (``refit_every`` observations, and whenever the buffer grows a bucket),
    with the factor rebuilt once per refit.  Host numpy mirrors of X/y are
    kept for candidate generation, de-duplication and tests — they never
    ride the hot dispatch path.
    """

    def __init__(
        self,
        d: int,
        kernel: str = "matern32",
        noise: float = 1e-4,
        fit_hypers: bool = True,
        refit_every: int = 8,
    ):
        if kernel not in _KERNELS:
            raise ValueError(f"unknown kernel {kernel!r}")
        self.d = d
        self.kernel = kernel
        self.fit_hypers = fit_hypers
        self.refit_every = refit_every
        self.max_n = MIN_BUCKET
        self.n = 0
        self._Xb = np.zeros((self.max_n, d), dtype=np.float64)
        self._yb = np.zeros(self.max_n, dtype=np.float64)
        self._index: Dict[bytes, int] = {}  # encoded-row bytes → buffer row
        # device-resident state (built lazily at first ensure_ready)
        self._L = None
        self._Xd = self._yd = self._maskd = self._thetad = None
        # (ls, sv, nv) — same defaults as the numpy reference
        self.theta = np.array([0.3, 1.0, noise], dtype=np.float64)
        self._tells_since_refit = 0
        self._hypers_fresh = not fit_hypers
        self.refactorizations = 0  # full factor builds — observability for tests

    # -- views ---------------------------------------------------------------
    @property
    def X(self) -> np.ndarray:
        return self._Xb[: self.n]

    @property
    def y(self) -> np.ndarray:
        return self._yb[: self.n]

    def incumbent(self) -> np.ndarray:
        return self.X[int(np.argmin(self.y))]

    # -- ingest --------------------------------------------------------------
    def observe(self, x: np.ndarray, y: float) -> None:
        """Fold one (encoded config, value) pair into the surrogate state."""
        x = np.ascontiguousarray(np.asarray(x, dtype=np.float64))
        y = float(y)
        key = x.tobytes()
        row = self._index.get(key)
        fns = _compiled(self.kernel)
        if row is not None:
            # Duplicate encoding: keep the best observation for this row.
            val = min(self._yb[row], y)
            self._yb[row] = val
            if self._L is not None:
                with enable_x64():
                    self._yd = fns["set_y"](self._yd, row, val)
            return
        if self.n == self.max_n:
            self._grow()
        i = self.n
        self._Xb[i] = x
        self._yb[i] = y
        self._index[key] = i
        if self._L is not None:
            with enable_x64():
                self._L, self._Xd, self._yd, self._maskd = fns["append"](
                    self._L, self._Xd, self._yd, self._maskd,
                    jnp.asarray(x), y, self._thetad)
        self.n = i + 1
        self._tells_since_refit += 1
        if self.fit_hypers and self._tells_since_refit >= self.refit_every:
            self._hypers_fresh = False

    def seed_observations(self, X: np.ndarray, y: np.ndarray) -> int:
        """Bulk-inject prior (encoded config, value) pairs — warm-start path.

        Cross-context transfer (campaign warm starts) arrives as a block of
        observations from the nearest tuned context.  Loading them through
        N ``observe`` calls would pay N rank-1 device dispatches before the
        first real tell; instead the rows land straight in the padded host
        buffers (growing the power-of-two bucket once, to fit them all) and
        the resident factor is invalidated, so the next ``ensure_ready``
        re-uploads and refactors exactly once.  Duplicate encodings fold
        keep-best, same as ``observe``.  Returns the number of *new* rows.
        """
        X = np.atleast_2d(np.asarray(X, dtype=np.float64))
        y = np.asarray(y, dtype=np.float64).reshape(-1)
        if X.shape[0] != y.shape[0] or X.shape[1] != self.d:
            raise ValueError(f"seed_observations: shapes {X.shape}/{y.shape} "
                             f"do not match d={self.d}")
        added = 0
        changed = False
        for xi, yi in zip(X, y):
            xi = np.ascontiguousarray(xi)
            key = xi.tobytes()
            row = self._index.get(key)
            if row is not None:
                if float(yi) < self._yb[row]:
                    self._yb[row] = float(yi)
                    changed = True  # host y moved: resident _yd is now stale
                continue
            while self.n + 1 > self.max_n:
                self._grow()
            self._Xb[self.n] = xi
            self._yb[self.n] = float(yi)
            self._index[key] = self.n
            self.n += 1
            added += 1
        if added or changed:
            # One re-upload (+ refactor) at next ensure_ready picks up both
            # the new rows and any keep-best folds into existing rows.
            self._L = None
            if self.fit_hypers:
                self._hypers_fresh = False
        return added

    def _grow(self) -> None:
        self.max_n *= 2
        Xb = np.zeros((self.max_n, self.d), dtype=np.float64)
        yb = np.zeros(self.max_n, dtype=np.float64)
        Xb[: self.n] = self._Xb
        yb[: self.n] = self._yb
        self._Xb, self._yb = Xb, yb
        self._L = None  # next ensure_ready re-uploads + refactors at the new bucket
        if self.fit_hypers:
            self._hypers_fresh = False

    # -- fitting -------------------------------------------------------------
    def _upload(self) -> None:
        mask = np.zeros(self.max_n, dtype=np.float64)
        mask[: self.n] = 1.0
        self._Xd = jnp.asarray(self._Xb)
        self._yd = jnp.asarray(self._yb)
        self._maskd = jnp.asarray(mask)
        self._thetad = jnp.asarray(self.theta)

    def ensure_ready(self) -> None:
        """Refit hypers if due, rebuild the factor if missing (one dispatch
        each, amortized across many observes)."""
        if self.n == 0:
            raise RuntimeError("observe() first")
        fns = _compiled(self.kernel)
        with enable_x64():
            if self._L is None:
                self._upload()
            if self.fit_hypers and not self._hypers_fresh and self.n >= 4:
                theta0s = jnp.asarray(
                    [np.log([ls0, 1.0, max(self.theta[2], 1e-6)])
                     for ls0 in _LS_STARTS])
                self._thetad = fns["fit_hypers"](
                    self._Xd, self._maskd, self._yd, theta0s)
                self.theta = np.asarray(self._thetad)
                self._hypers_fresh = True
                self._tells_since_refit = 0
                self._L = None
            if self._L is None:
                self._L = fns["full_chol"](self._Xd, self._maskd, self._thetad)
                self.refactorizations += 1

    # -- suggest -------------------------------------------------------------
    def _suggest_args(self, cand: np.ndarray) -> Tuple:
        """Device argument tuple for the fused suggest — everything resident
        but the pool (x64 enforced: outside the context jnp.asarray would
        silently downcast to f32).  Call ensure_ready first."""
        with enable_x64():
            return (self._L, self._Xd, self._maskd, self._yd, self._thetad,
                    jnp.asarray(_pad_pool(cand)))

    def suggest(self, cand: np.ndarray, acq: str = "ei",
                ucb_beta: float = 2.0) -> Tuple[int, np.ndarray]:
        """Score the pool, return (argmax index, scores[:len(cand)])."""
        self.ensure_ready()
        fn = _suggest_fns(self.kernel, 1 if acq == "ucb" else 0, ucb_beta)["jit"]
        with enable_x64():
            idx, scores = fn(*self._suggest_args(cand))
        return int(idx), np.asarray(scores)[: len(cand)]


# ------------------------------------------------------------- batched asks
def _jax_model_ready(opt: Any) -> bool:
    """True when ``opt`` is a jax-backed BayesOpt past its init phase (duck-
    typed to avoid an import cycle with bayesopt.py).  Optimizers exposing
    ``model_ready`` (warm-started BO, where injected priors shorten the init
    phase) decide for themselves."""
    if getattr(opt, "backend", None) != "jax" or not hasattr(opt, "_model_inputs"):
        return False
    ready = getattr(opt, "model_ready", None)
    if ready is not None:
        return bool(ready)
    return len(getattr(opt, "history", ())) >= getattr(opt, "n_init", 1 << 30)


class BatchedBayesOpt:
    """One device dispatch for N sessions' suggestions.

    Groups jax-backed :class:`~.bayesopt.BayesOpt` optimizers by compiled
    signature (kernel, acquisition, d, bucket, pool), stacks their resident
    state along a session axis and runs the fused vmapped suggest once per
    group.  Optimizers that are still in their init phase (or are not jax BO
    at all) fall back to their own ``ask`` — the result is element-wise
    identical to sequential asks.
    """

    def __init__(self, opts: Sequence[Any]):
        self.opts = list(opts)

    def ask_all(self) -> List[Dict[str, Any]]:
        out: List[Optional[Dict[str, Any]]] = [None] * len(self.opts)
        groups: Dict[Tuple, List[Tuple[int, Any, np.ndarray, Tuple]]] = {}
        for i, opt in enumerate(self.opts):
            if not _jax_model_ready(opt):
                out[i] = opt.ask()
                continue
            eng, cand, acq_id, beta = opt._model_inputs()
            eng.ensure_ready()
            cand = _pad_pool(cand)
            state = (eng._L, eng._Xd, eng._maskd, eng._yd, eng._thetad)
            sig = (eng.kernel, acq_id, beta, eng.d, eng.max_n, len(cand))
            groups.setdefault(sig, []).append((i, opt, cand, state))
        for (kernel, acq_id, beta, _, _, _), members in groups.items():
            with enable_x64():
                if len(members) == 1:
                    i, opt, cand, state = members[0]
                    fn = _suggest_fns(kernel, acq_id, beta)["jit"]
                    idxs = [fn(*state, jnp.asarray(cand))[0]]
                else:
                    # One pool upload + one fused dispatch for the whole
                    # group.  The session axis is padded to a power of two
                    # (duplicating the last member) so a mux whose
                    # ready-to-ask count varies 2..N per poll compiles
                    # log2(N) batched programs per signature, not N.
                    S = len(members)
                    P = 1 << (S - 1).bit_length()
                    states = tuple(m[3] for m in members)
                    states = states + (states[-1],) * (P - S)
                    pools = [m[2] for m in members]
                    pools = pools + [pools[-1]] * (P - S)
                    cands = jnp.asarray(np.stack(pools))
                    idxs = _batched_suggest_fn(kernel, acq_id, beta)(states, cands)
                    idxs = idxs[:S]
            for (i, opt, cand, _), idx in zip(members, np.asarray(idxs)):
                out[i] = opt.space.validate(opt.space.decode(cand[int(idx)]))
        return out  # type: ignore[return-value]


def batched_ask(opts: Sequence[Any]) -> List[Dict[str, Any]]:
    """Convenience: one-shot :class:`BatchedBayesOpt` over ``opts``."""
    return BatchedBayesOpt(opts).ask_all()
