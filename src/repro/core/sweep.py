"""Batched multi-config hyper-parameter sweeps (vmap-over-configs).

The paper selects between polarization models by training many SVM
variants and comparing confusion matrices (Tablo 6/8); its whole point
is amortizing training cost across a cluster. The same argument applies
across *jobs*: S (C, kernel-scale) configurations are embarrassingly
parallel, so instead of S sequential ``fit_mapreduce`` calls — S traces,
S compiles, S device round-trips per round — we lift the value-like
hyper-parameters into the traced :class:`~repro.core.svm.SolverParams`
pytree and run every config under one outer ``vmap``: one jit, one
device pass, S models (He et al. 2019 make the batched-solver-instances
case for modern hardware).

Per-config convergence (eq. 8) is masked, not synchronized:

* driver level — a host-side ``done`` mask freezes a finished config's
  SV buffer and best hypothesis, and the round loop exits when every
  config has converged;
* solver level — a finished config's ``tol`` is rewritten to ``+inf``
  (it is traced, so this costs nothing), which makes its dual-CD
  ``while_loop`` predicate go false after a single epoch; under
  ``vmap`` the while_loop batching rule then select-freezes that lane
  while unconverged configs keep iterating. Finished configs stop
  contributing work.

One-vs-rest multiclass folds into the same batch axis: k classes × S
configs are k·S independent binary jobs (:func:`fit_one_vs_rest_sweep`).

Two execution modes mirror :mod:`repro.core.mapreduce_svm`:

* **functional** (:func:`fit_mapreduce_sweep`) — configs on a leading
  ``vmap`` axis over :func:`mapreduce_round`;
* **sharded** (:func:`build_sharded_sweep_round`) — the same ``vmap``
  *inside* the ``shard_map`` round body, so each device solves S local
  subproblems per round and the all-gather shuffle moves S buffers in
  one collective.
"""
from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, faults
from repro.analysis.hostsync import allowed_host_sync
from repro.analysis.retrace import no_retrace
from repro import sparse as sparse_rows
from repro.core.mapreduce_svm import (PACKED_SHUFFLES, MRSVMConfig,
                                      SVBuffer, _device_risks, _hop_plan,
                                      _merge_hops, _round_candidates,
                                      init_sv_buffer, make_sharded_round,
                                      mapreduce_round, pack_wire_rows,
                                      resolve_topology, unpack_wire_rows)
from repro.core.svm import (BinarySVM, SolverParams, SVMConfig,
                            decision_kernel, fit_binary)


class SweepResult(NamedTuple):
    """Converged state of every config in the sweep (leading axis S)."""
    params: SolverParams   # (S,)-batched hyper-parameters
    risks: jax.Array       # (S,) best R_emp per config over its rounds
    ws: jax.Array          # (S, d) best linear hypothesis per config
    bs: jax.Array          # (S,)
    sv: SVBuffer           # (S, cap, …) converged SV_global per config
    final: BinarySVM       # (S, …) models retrained on SV_global alone
    rounds: np.ndarray     # (S,) rounds each config ran before eq. 8
    history: Tuple[dict, ...]

    @property
    def num_configs(self) -> int:
        return int(self.risks.shape[0])

    @property
    def best(self) -> int:
        """Index of the sweep-selected config (min empirical risk)."""
        return int(np.argmin(np.asarray(self.risks)))


# ---------------------------------------------------------------------------
# Building batched SolverParams.
# ---------------------------------------------------------------------------

def stack_params(params_list: Sequence[SolverParams]) -> SolverParams:
    """Stack per-config params into one (S,)-batched pytree."""
    if not params_list:
        raise ValueError("empty sweep")
    return compat.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def sweep_grid(cfg: SVMConfig,
               C: Optional[Sequence[float]] = None,
               gamma: Optional[Sequence[float]] = None,
               tol: Optional[Sequence[float]] = None,
               sv_threshold: Optional[Sequence[float]] = None,
               coef0: Optional[Sequence[float]] = None,
               max_epochs: Optional[Sequence[int]] = None) -> SolverParams:
    """Cartesian grid over the traced hyper-params, defaults from ``cfg``.

    Returns a (S,)-batched :class:`SolverParams` with
    S = Π len(axis). Axis order is C-major, matching
    ``itertools.product(C, gamma, tol, sv_threshold, coef0, max_epochs)``.
    ``max_epochs`` entries are traced *cutoffs*: they can only tighten
    the static ``cfg.max_epochs`` loop bound (DESIGN.md §8).
    """
    base = cfg.params()
    axes = [np.atleast_1d(np.asarray(v, np.float32)) if v is not None
            else np.asarray([float(dflt)], np.float32)
            for v, dflt in ((C, base.C), (gamma, base.gamma),
                            (tol, base.tol),
                            (sv_threshold, base.sv_threshold),
                            (coef0, base.coef0),
                            (max_epochs, base.max_epochs))]
    grid = np.meshgrid(*axes, indexing="ij")
    flat = [jnp.asarray(g.reshape(-1)) for g in grid]
    c, g, t, s, c0, me = flat
    return SolverParams(C=c, tol=t, sv_threshold=s, gamma=g, coef0=c0,
                        max_epochs=me)


def _num_configs(params: SolverParams) -> int:
    S = params.C.shape[0]
    for leaf in params:
        if leaf.ndim != 1 or leaf.shape[0] != S:
            raise ValueError("sweep params must share one leading (S,) axis; "
                             f"got shapes {[l.shape for l in params]}")
    return int(S)


def _freeze(done: np.ndarray, old, new):
    """Per-config select: keep ``old`` state where ``done`` (leading S)."""
    d = jnp.asarray(done)
    sel = lambda o, n: jnp.where(d.reshape((-1,) + (1,) * (n.ndim - 1)), o, n)
    return compat.tree_map(sel, old, new)


def _run_rounds(step, svb, d: int, cfg: MRSVMConfig,
                params: SolverParams, verbose: bool, tag: str,
                snapshot=None, fail_on_retrace: bool = False):
    """Shared eq. 8-masked host round loop of both sweep modes.

    ``step(svb, eff_params) -> (sv_new, r_star (S,), ws (S, d), bs (S,))``
    where r_star/ws/bs are already reduced to each config's best
    reducer. Finished configs get ``tol=+inf`` AND an epoch cutoff of 0
    (their solver while_loop runs ZERO epochs; vmap select-freezes the
    lane) and their SV buffer / best hypothesis frozen on the host; the
    loop exits when every config has converged.

    ``snapshot`` handles round states that are NOT per-config buffers
    (the dedup ring's shared-row :class:`DedupChunk`): the raw state
    threads through ``step`` unfrozen — finished configs must be inert
    in the round itself, which the 0-epoch cutoff guarantees (their
    candidates die, so they can neither claim unique slots nor change
    active configs' results) — and ``snapshot(state)`` materializes the
    per-config (S, cap, …) buffer ONLY on rounds where a config
    converges (its frozen view) and on the last active round, keeping
    the expansion off the per-round hot path.

    Invariant hooks (DESIGN.md §14): the per-round device→host
    readbacks (risks, improved hypotheses) are the loop's DESIGNED sync
    points and run under ``allowed_host_sync``, so a caller-armed
    ``no_implicit_host_sync`` guard passes them while catching any
    stray transfer. ``fail_on_retrace`` arms the retrace detector on
    every round past the first: steady-state rounds must hit the jit
    cache (round 0 compiles; a convergence round's ``snapshot``
    expansion is off the hot path by design and stays outside the
    guard).
    """
    S = _num_configs(params)
    done = np.zeros(S, bool)
    prev = np.full(S, np.inf)
    best_risk = np.full(S, np.inf)
    best_w = np.zeros((S, d), np.float32)
    best_b = np.zeros(S, np.float32)
    rounds = np.zeros(S, np.int64)
    history = []
    frozen = None if snapshot is not None else svb
    inf = jnp.asarray(np.inf, params.tol.dtype)
    for t in range(cfg.max_rounds):
        with jax.profiler.TraceAnnotation("mr.round", round=t):
            guard = (no_retrace(f"[{tag}] steady-state round {t}")
                     if fail_on_retrace and t >= 1
                     else contextlib.nullcontext())
            with guard:
                dmask = jnp.asarray(done)
                eff = params._replace(
                    tol=jnp.where(dmask, inf, params.tol),
                    max_epochs=jnp.where(dmask, 0.0, params.max_epochs))
                sv_new, r_star, ws, bs = step(svb, eff)
                if snapshot is None:
                    frozen = _freeze(done, frozen, sv_new)
                svb = frozen if snapshot is None else sv_new
                with allowed_host_sync("eq. 8 convergence readback"), \
                        jax.profiler.TraceAnnotation("mr.eq8", round=t):
                    r_star = np.asarray(r_star)
            act = ~done
            faults.check_finite_risks(r_star, where=f"{tag} round {t}",
                                      mask=act)
            improved = act & (r_star < best_risk)
            if improved.any():
                with allowed_host_sync("improved-hypothesis readback"):
                    best_w[improved] = np.asarray(ws)[improved]
                    best_b[improved] = np.asarray(bs)[improved]
                best_risk = np.where(improved, r_star, best_risk)
            rounds[act] += 1
            history.append({"round": t, "risks": np.where(act, r_star, np.nan),
                            "active": int(act.sum())})
            if verbose:
                print(f"[{tag}] round={t} active={int(act.sum())}/{S} "
                      f"best_R_emp={np.nanmin(np.where(act, r_star, np.nan)):.5f}")
            newly = act & (t > 0) & (np.abs(prev - r_star) <= cfg.gamma)  # eq. 8
            if snapshot is not None and (newly.any()
                                         or t == cfg.max_rounds - 1):
                exp = snapshot(sv_new)
                frozen = exp if frozen is None else _freeze(done, frozen, exp)
            done |= newly
            prev = np.where(act, r_star, prev)
            if done.all():
                break
    return frozen, best_risk, best_w, best_b, rounds, tuple(history)


# ---------------------------------------------------------------------------
# Functional sweep driver.
# ---------------------------------------------------------------------------

# Module-level jits keyed on the frozen cfg (+ which inputs carry the
# (S,) job axis): repeated sweep calls with the same shapes hit the jit
# cache — the streaming service folds a wave per admission, and a
# per-call ``jax.jit`` would retrace every wave (see the twin note in
# repro.core.mapreduce_svm).
@functools.partial(jax.jit, static_argnames=("cfg", "x_ax", "m_ax", "L"))
def _sweep_round_jit(X, ypb, maskp, sv_b, eff, cfg, x_ax, m_ax, L):
    # X stays (…, n, d) outside the program: partitioning it here keeps
    # the caller's rows the only resident copy.
    Xp = _partition_rows(X, L)
    out = jax.vmap(
        lambda Xq, yp, mp, sv, p: mapreduce_round(
            Xq, yp, mp, sv, cfg, params=p),
        in_axes=(x_ax, 0, m_ax, 0, 0))(Xp, ypb, maskp, sv_b, eff)
    # The per-config best-reducer pick (eq. 7) happens ON DEVICE so the
    # host transfer is (S, d), not the full (S, L, d) hypothesis tensor.
    l_star = jnp.argmin(out.risks, axis=1)               # (S,)
    r_sel = jnp.take_along_axis(out.risks, l_star[:, None], 1)[:, 0]
    w_sel = jnp.take_along_axis(out.ws, l_star[:, None, None], 1)[:, 0]
    b_sel = jnp.take_along_axis(out.bs, l_star[:, None], 1)[:, 0]
    return out.sv, r_sel, w_sel, b_sel


def _partition_rows(X, L: int):
    """(…, n, d) rows → (…, L, per, d), zero-padding n to L·per."""
    n, d = X.shape[-2], X.shape[-1]
    per = -(-n // L)
    lead = tuple(X.shape[:-2])
    return sparse_rows.pad_rows(X, L * per - n).reshape(*lead, L, per, d)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _sweep_final_jit(svb: SVBuffer, params: SolverParams, cfg):
    return jax.vmap(
        lambda sv, p: fit_binary(sv.x, sv.y, sv.mask, cfg.svm, params=p))(
            svb, params)


def fit_mapreduce_sweep(X: jax.Array, y: jax.Array, num_partitions: int,
                        cfg: MRSVMConfig, params: SolverParams,
                        mask: Optional[jax.Array] = None,
                        verbose: bool = False,
                        fail_on_retrace: bool = False) -> SweepResult:
    """Run S MapReduce-SVM jobs in one batched computation.

    Every data input is either shared or carries a leading (S,) job
    axis: ``X`` is ``(n, d)`` (shared) or ``(S, n, d)`` (per-job rows —
    the multi-tenant streaming fold); ``y`` is ``(n,)`` or ``(S, n)``
    (per-job labels — the one-vs-rest folding); ``mask`` is ``None``,
    ``(n,)`` or ``(S, n)``. Per-config eq. 8 masking freezes converged
    configs (see module docstring); each config's trajectory is
    identical to a sequential ``fit_mapreduce`` call with its
    ``params``/data slice.
    """
    with jax.profiler.TraceAnnotation("mr.fit"):
        S = _num_configs(params)
        n, d = X.shape[-2], X.shape[-1]
        L = num_partitions
        per = -(-n // L)
        pad = L * per - n
        if X.ndim == 3 and X.shape[0] != S:
            raise ValueError(f"per-job X has leading axis {X.shape[0]}, "
                             f"expected S={S}")
        x_ax = 0 if X.ndim == 3 else None
        yb = jnp.broadcast_to(jnp.atleast_2d(y.astype(X.dtype)), (S, n))
        ypb = jnp.pad(yb, ((0, 0), (0, pad))).reshape(S, L, per)
        base_mask = (jnp.ones((n,), X.dtype) if mask is None
                     else mask.astype(X.dtype))
        if base_mask.ndim == 2:
            maskp = jnp.pad(base_mask, ((0, 0), (0, pad))).reshape(S, L, per)
            m_ax = 0
        else:
            maskp = jnp.pad(base_mask, (0, pad)).reshape(L, per)
            m_ax = None

        sv0 = init_sv_buffer(
            cfg.sv_capacity, d, X.dtype,
            nnz_cap=X.nnz_cap if sparse_rows.is_sparse(X) else None)
        svb = compat.tree_map(
            lambda a: jnp.broadcast_to(a, (S,) + a.shape), sv0)

        def step(sv_b, eff):
            return _sweep_round_jit(X, ypb, maskp, sv_b, eff,
                                    cfg=cfg, x_ax=x_ax, m_ax=m_ax, L=L)

        svb, best_risk, best_w, best_b, rounds, history = _run_rounds(
            step, svb, d, cfg, params, verbose, "sweep",
            fail_on_retrace=fail_on_retrace)

        # Final consolidated models: retrain each config on its SV_global.
        with jax.profiler.TraceAnnotation("mr.final"):
            final = _sweep_final_jit(svb, params, cfg=cfg)
        return SweepResult(params=params, risks=jnp.asarray(best_risk),
                           ws=jnp.asarray(best_w), bs=jnp.asarray(best_b),
                           sv=svb, final=final, rounds=rounds, history=history)


def sweep_decision_values(res: SweepResult, X: jax.Array,
                          cfg: MRSVMConfig) -> jax.Array:
    """(S, n) decision values of every config's final model on ``X``."""
    if cfg.svm.kernel.name == "linear" and not cfg.svm.use_gram:
        if sparse_rows.is_sparse(X):
            return (X @ res.final.w.T).T + res.final.b[:, None]
        return jnp.einsum("nd,sd->sn", X, res.final.w) + res.final.b[:, None]

    def one(sv, alpha, b, p):
        coef = alpha * sv.y * sv.mask
        return decision_kernel(sv.x, coef, b, X, cfg.svm.kernel,
                               gamma=p.gamma, coef0=p.coef0)
    return jax.vmap(one)(res.sv, res.final.alpha, res.final.b, res.params)


def predict_sweep(res: SweepResult, X: jax.Array,
                  cfg: MRSVMConfig) -> jax.Array:
    """(S, n) ±1 predictions of every config's final model."""
    return jnp.where(sweep_decision_values(res, X, cfg) >= 0, 1.0, -1.0)


# ---------------------------------------------------------------------------
# One-vs-rest folded into the batch axis.
# ---------------------------------------------------------------------------

class SweepOneVsRest(NamedTuple):
    """k classes × S configs trained as one k·S-job batch.

    Job ``j`` is (config ``j // k``, class ``classes[j % k]``).
    """
    classes: Tuple[int, ...]
    num_configs: int
    result: SweepResult
    cfg: MRSVMConfig

    def decision_tensor(self, X: jax.Array) -> jax.Array:
        """(S, k, n) one-vs-rest decision values."""
        k = len(self.classes)
        dm = sweep_decision_values(self.result, X, self.cfg)   # (k*S, n)
        return dm.reshape(self.num_configs, k, X.shape[0])

    def predict(self, X: jax.Array) -> jax.Array:
        """(S, n) class labels per config (argmax over the k scores)."""
        idx = jnp.argmax(self.decision_tensor(X), axis=1)
        return jnp.asarray(self.classes)[idx]

    def risks(self) -> np.ndarray:
        """(S,) mean over the k binary jobs' best risks — the sweep's
        per-config model-selection score."""
        k = len(self.classes)
        return np.asarray(self.result.risks).reshape(
            self.num_configs, k).mean(axis=1)

    @property
    def best(self) -> int:
        return int(np.argmin(self.risks()))


def fit_one_vs_rest_sweep(X: jax.Array, y: jax.Array,
                          classes: Sequence[int], num_partitions: int,
                          cfg: MRSVMConfig, params: SolverParams,
                          verbose: bool = False) -> SweepOneVsRest:
    """One-vs-rest multiclass × hyper-param sweep as a single batch."""
    k = len(classes)
    S = _num_configs(params)
    y1 = jnp.stack([jnp.where(y == c, 1.0, -1.0).astype(X.dtype)
                    for c in classes])                       # (k, n)
    y_jobs = jnp.tile(y1, (S, 1))                            # (k*S, n)
    pj = compat.tree_map(lambda a: jnp.repeat(a, k, axis=0), params)
    res = fit_mapreduce_sweep(X, y_jobs, num_partitions, cfg, pj,
                              verbose=verbose)
    return SweepOneVsRest(classes=tuple(int(c) for c in classes),
                          num_configs=S, result=res, cfg=cfg)


# ---------------------------------------------------------------------------
# Cross-config SV dedup: the ring sweep's wire format (DESIGN.md §10).
# ---------------------------------------------------------------------------

class DedupChunk(NamedTuple):
    """Deduplicated per-device candidate chunk of a sweep round.

    S configs solving the SAME sharded data converge onto overlapping
    support sets — the margin of the data doesn't move much across
    nearby (C, γ). Shipping every config's (k, d) candidate rows
    therefore moves each shared row S times. The dedup layout collapses
    the chunk to its *unique home rows* plus per-config sidebands:

      x (U, d)      unique feature rows (wire dtype), each shipped once
      y (U,)        labels of the unique rows
      ids (U,)      global row ids (-1 on dead slots)
      ptr (S, k)    each config's j-th candidate → its unique slot (-1
                    when dead or evicted)
      alpha (S, k)  per-config α columns (full precision, never shared)
      mask (S, k)   per-config live flags

    Payload: U·d rows instead of S·k·d — the S× row traffic stops
    scaling in duplicated rows. With ``U = min(S·k, per)`` (the
    default) no live row can ever be evicted, so
    :func:`expand_chunk` ∘ :func:`dedup_candidates` is lossless
    (hypothesis-tested in ``tests/test_property.py``); a smaller
    explicit ``dedup_max_unique`` trades eviction of the
    lowest-evidence unique rows for wire bytes, the same
    capacity-bounding the SV buffer itself applies.
    """
    x: jax.Array
    y: jax.Array
    ids: jax.Array
    ptr: jax.Array
    alpha: jax.Array
    mask: jax.Array


def dedup_unique_cap(cfg: MRSVMConfig, num_configs: int, k: int,
                     per: int) -> int:
    """Unique-row slots a device ships per round (see DedupChunk)."""
    if cfg.dedup_max_unique is not None:
        return max(1, min(cfg.dedup_max_unique, num_configs * k, per))
    return min(num_configs * k, per)


def dedup_candidates(cand: SVBuffer, Xl: jax.Array, yl: jax.Array,
                     idx, per: int, unique_cap: int,
                     wire_dtype=jnp.bfloat16) -> DedupChunk:
    """Collapse (S, k) candidate chunks to unique home rows + sidebands.

    ``cand`` leaves carry a leading (S, k) config axis; all its ids
    point into THIS device's home rows ``[idx·per, (idx+1)·per)``, so a
    (per,)-slot scoreboard (max α across configs = eviction priority)
    finds the unique set without sorting. Assumes ``sv_threshold ≥ 0``
    (live candidates have α > 0), which the solver's box constraint
    already guarantees.
    """
    live = cand.mask > 0
    r = jnp.where(live, cand.ids - idx * per, 0)          # local row ids
    score = jnp.zeros((per,), jnp.float32).at[r].max(
        jnp.where(live, cand.alpha.astype(jnp.float32), 0.0))
    U = unique_cap
    top_score, top_r = jax.lax.top_k(score, U)            # evidence-ranked
    live_u = top_score > 0
    slot = jnp.where(live_u, jnp.arange(U, dtype=jnp.int32), -1)
    inv = jnp.full((per,), -1, jnp.int32).at[top_r].set(slot)
    return DedupChunk(
        x=(Xl[top_r] * live_u[:, None].astype(Xl.dtype)).astype(wire_dtype),
        y=yl[top_r] * live_u.astype(yl.dtype),
        ids=jnp.where(live_u, (idx * per + top_r).astype(jnp.int32), -1),
        ptr=jnp.where(live, inv[r], -1),
        alpha=cand.alpha,
        mask=cand.mask)


def expand_chunk(chunk: DedupChunk, buf_dtype=jnp.float32) -> SVBuffer:
    """Inverse of :func:`dedup_candidates`: per-config (S, k) chunks.

    Candidates whose unique row was evicted (``ptr == -1``) come back
    dead; with the lossless default capacity that never happens and the
    round-trip reproduces the undeduplicated chunks exactly (up to the
    wire-dtype round-trip of ``x``).
    """
    safe = jnp.maximum(chunk.ptr, 0)
    valid = jnp.logical_and(chunk.ptr >= 0, chunk.mask > 0)
    vf = valid.astype(buf_dtype)
    return SVBuffer(
        x=chunk.x[safe].astype(buf_dtype) * vf[..., None],
        y=chunk.y[safe].astype(buf_dtype) * vf,
        alpha=chunk.alpha.astype(buf_dtype) * vf,
        ids=jnp.where(valid, chunk.ids[safe], -1),
        mask=vf)


# ---------------------------------------------------------------------------
# Sharded sweep: vmap-over-configs inside the shard_map round body.
# ---------------------------------------------------------------------------

def uses_dedup_state(cfg: MRSVMConfig, per_config_data: bool) -> bool:
    """True when the sharded sweep's SV state IS the dedup wire format.

    Both packed transports (ring and hier) ship and store the shared
    rows once — the dedup layout is a property of the wire format, not
    of the hop schedule. Per-config-data waves (streams with distinct
    rows) keep per-config buffers — their global ids index different
    datasets, so cross-config dedup has no shared rows to collapse.
    """
    return (cfg.shuffle_impl in PACKED_SHUFFLES and cfg.sweep_dedup
            and not per_config_data)


def init_sharded_sweep_sv(cfg: MRSVMConfig, num_configs: int, d: int,
                          num_devices: int, rows_per_device: int,
                          dtype=jnp.float32, per_config_data: bool = False):
    """Empty round-0 SV state of the sharded sweep.

    Allgather rounds carry the (S, cap, …) :class:`SVBuffer`; the dedup
    packed transports (ring/hier) carry the shared-row
    :class:`DedupChunk` state directly — the expanded per-config buffer
    never materializes between rounds (DESIGN.md §10); per-config-data
    packed rounds keep per-config buffers with wire-dtype feature rows.
    """
    cap = cfg.sv_capacity
    nnzc = (cfg.svm.nnz_cap if cfg.svm.row_format == "sparse_csr"
            else None)
    if uses_dedup_state(cfg, per_config_data):
        k = cap // num_devices
        U = dedup_unique_cap(cfg, num_configs, k, rows_per_device)
        R = num_devices * U
        wire_dt = jnp.dtype(cfg.shuffle_wire_dtype)
        if nnzc is None:
            x0 = jnp.zeros((R, d), wire_dt)
        else:
            x0 = sparse_rows.SparseRows(
                jnp.zeros((R, nnzc), jnp.int32),
                jnp.zeros((R, nnzc), wire_dt), d)
        return DedupChunk(
            x=x0,
            y=jnp.zeros((R,), dtype),
            ids=jnp.full((R,), -1, jnp.int32),
            ptr=jnp.full((num_configs, cap), -1, jnp.int32),
            alpha=jnp.zeros((num_configs, cap), dtype),
            mask=jnp.zeros((num_configs, cap), dtype))
    sv0 = init_sv_buffer(cap, d, dtype, nnz_cap=nnzc)
    if cfg.shuffle_impl in PACKED_SHUFFLES:
        sv0 = sv0._replace(
            x=sv0.x.astype(jnp.dtype(cfg.shuffle_wire_dtype)))
    return compat.tree_map(
        lambda a: jnp.broadcast_to(a, (num_configs,) + a.shape), sv0)


def _state_views(state: DedupChunk, buf_dt):
    """Per-config :class:`SVBuffer` views of the shared-row state.

    Only the (S, cap) sidebands are per-config; the (cap, d) feature
    rows of config s are gathered from the shared unique rows — the
    same read volume the expanded buffer would cost, from a buffer
    S× smaller (and in the wire dtype).
    """
    def view(ptr_s, alpha_s, mask_s):
        safe = jnp.maximum(ptr_s, 0)
        valid = jnp.logical_and(ptr_s >= 0, mask_s > 0)
        vf = valid.astype(buf_dt)
        return SVBuffer(
            x=state.x[safe] * vf[:, None].astype(state.x.dtype),
            y=state.y[safe].astype(buf_dt) * vf,
            alpha=alpha_s.astype(buf_dt) * vf,
            ids=jnp.where(valid, state.ids[safe], -1),
            mask=vf)
    return view


def _make_packed_sweep_body(cfg: MRSVMConfig, axes, ndev: int, per: int,
                            per_config_data: bool):
    """Packed-wire sweep round: one transport for all S configs.

    The per-config solve/top-k (vmapped :func:`_round_candidates`) is
    followed by ONE pass of the shared hop engine
    (:func:`repro.core.mapreduce_svm._merge_hops`) over the round's
    wire payload — the stage's permutation is in flight while the
    arrived chunks are written into the assembling state and their S
    hypotheses are scored (eq. 7). The hop schedule is the transport's
    (ring: ndev single-message stages; hier: host-stages of
    ndev//hosts messages, DESIGN.md §16) — the wire format is the
    same. On shared-data sweeps the SV state IS the cross-config dedup
    format (:class:`DedupChunk` with ptr rebased to the global slot
    axis): unique rows are shipped AND stored once, so neither the
    wire nor the replicated round state scales in duplicated rows —
    the (S, cap, d) per-config buffer exists only as transient
    per-config gathers inside the reducer augment. Per-config-data
    waves (streams with distinct rows — ids aren't comparable) keep
    per-config buffers and ship the plain chunk with wire-dtype
    feature rows.
    """
    cap = cfg.sv_capacity
    k = cap // ndev
    wire_dt = jnp.dtype(cfg.shuffle_wire_dtype)
    dedup = uses_dedup_state(cfg, per_config_data)
    hosts = resolve_topology(cfg, ndev)

    def sweep_body(Xl, yl, ml, sv_state, params_b: SolverParams):
        idx = compat.axis_index(axes)
        S = params_b.C.shape[0]
        buf_dt = Xl.dtype
        d = Xl.shape[-1]
        comp = lambda X1, y1, m1, sv, p: _round_candidates(
            X1, y1, m1, sv, cfg, axes, idx, k, per, p)
        if per_config_data:
            cand_b, w_b, b_b = jax.vmap(comp)(Xl, yl, ml, sv_state,
                                              params_b)
        elif dedup:
            view = _state_views(sv_state, buf_dt)
            cand_b, w_b, b_b = jax.vmap(
                lambda pt, al, mk, p: comp(Xl, yl, ml, view(pt, al, mk), p))(
                    sv_state.ptr, sv_state.alpha, sv_state.mask, params_b)
        else:
            cand_b, w_b, b_b = jax.vmap(
                lambda sv, p: comp(Xl, yl, ml, sv, p))(sv_state, params_b)

        # The wire payload stays in chunk format through the hops —
        # each stage's consumption is the eq. 7 scoring of the arrived
        # hypotheses; the state is assembled AFTER the last hop with
        # one roll (a per-stage dynamic-update-slice chain would
        # rewrite the whole state every hop). ONE coalesced f32 message
        # per hop — the bitcast-packed wire rows plus the sidebands and
        # hypotheses — because per-leaf permutes would pay the
        # collective's fixed rendezvous cost 8× per stage.
        f32 = jnp.float32
        nnzc = Xl.nnz_cap if sparse_rows.is_sparse(Xl) else None
        if dedup:
            U = dedup_unique_cap(cfg, S, k, per)
            chunk0 = dedup_candidates(cand_b, Xl, yl, idx, per, U, wire_dt)
            xf, wslots = pack_wire_rows(chunk0.x, wire_dt)
            n_rows = U
            side0 = jnp.concatenate([
                xf, chunk0.y.astype(f32), chunk0.ids.astype(f32),
                chunk0.ptr.astype(f32).reshape(-1),
                chunk0.alpha.astype(f32).reshape(-1),
                chunk0.mask.astype(f32).reshape(-1),
                w_b.astype(f32).reshape(-1), b_b.astype(f32)])
            o_w = U * wslots + 2 * U + 3 * S * k
        else:
            U = k
            xf, wslots = pack_wire_rows(
                cand_b.x.reshape(S * k, d), wire_dt)
            n_rows = S * k
            side0 = jnp.concatenate([
                xf,
                cand_b.y.astype(f32).reshape(-1),
                cand_b.alpha.astype(f32).reshape(-1),
                cand_b.mask.astype(f32).reshape(-1),
                cand_b.ids.astype(f32).reshape(-1),
                w_b.astype(f32).reshape(-1), b_b.astype(f32)])
            o_w = S * k * wslots + 4 * S * k
        o_x = n_rows * wslots
        plan = _hop_plan(cfg, axes, ndev, idx, hosts)
        m = plan.m

        def consume(blk):         # (m, L) arrived → (m, S, per) eq. 7
            wt = blk[:, o_w:o_w + S * d].reshape(m, S, d)
            bt = blk[:, o_w + S * d:].reshape(m, S)
            if per_config_data:
                if nnzc is not None:
                    s = jax.vmap(lambda w1: jax.vmap(
                        lambda xs, w2: xs @ w2)(Xl, w1))(wt) \
                        + bt[:, :, None]
                else:
                    s = jnp.einsum("spd,msd->msp", Xl, wt) \
                        + bt[:, :, None]
            elif nnzc is not None:
                s = (Xl @ wt.reshape(m * S, d).T).T.reshape(m, S, per) \
                    + bt[:, :, None]
            else:
                s = jnp.einsum("pd,msd->msp", Xl, wt) + bt[:, :, None]
            return s.astype(w_b.dtype)

        # Stage t carried origin group (gi-t) → device order is ONE
        # roll of the reversed-arrival concat (see _merge_hops's note).
        M, ordered = _merge_hops(side0, plan, consume)
        xs = unpack_wire_rows(M[:, :o_x], ndev * n_rows, d, wire_dt,
                              wslots, nnz_cap=nnzc)
        if not dedup:
            xs = xs.reshape(ndev, S, k, d).swapaxes(0, 1) \
                   .reshape(S, cap, d)
        acc = _assemble_chunks(xs, M, o_x, dedup, ndev, U, k, S, buf_dt)
        W = jnp.swapaxes(M[:, o_w:o_w + S * d].reshape(ndev, S, d), 0, 1)
        B = M[:, o_w + S * d:].T                     # (S, ndev)
        scores = jnp.transpose(ordered, (1, 2, 0))   # (S, per, ndev)

        if per_config_data:
            risks = jax.vmap(
                lambda sc, y1, m1: _device_risks(
                    sc, y1, m1, cfg, axes, ndev))(scores, yl, ml)
        else:
            risks = jax.vmap(
                lambda sc: _device_risks(
                    sc, yl, ml, cfg, axes, ndev))(scores)
        l_star = jnp.argmin(risks, axis=1)                   # (S,)
        w_sel = jnp.take_along_axis(W, l_star[:, None, None], axis=1)[:, 0]
        b_sel = jnp.take_along_axis(B, l_star[:, None], axis=1)[:, 0]
        return acc, risks, w_sel, b_sel

    return sweep_body


def _assemble_chunks(xs, M, o_x: int, dedup: bool, ndev: int, U: int,
                     k: int, S: int, buf_dt):
    """Device-order state from the ring's reordered messages.

    ``xs`` is the unpacked wire-dtype row buffer already in device
    order — (ndev·U, d) for dedup chunks, (S, ndev·k, d) for plain
    chunks — and ``M`` the (ndev, L) message matrix in device order
    with the packed sidebands starting at column ``o_x``. Dedup chunks:
    the per-config ptr columns are rebased onto the global slot axis
    (block o adds o·U). Plain chunks (per-config-data waves): sideband
    leaves concatenate into the (S, ndev·k) columns.
    """
    cap = ndev * k
    sides = M[:, o_x:]
    if dedup:
        col = lambda a, b: sides[:, a:b]
        ptr = col(2 * U, 2 * U + S * k).reshape(ndev, S, k)
        base = jnp.arange(ndev, dtype=jnp.float32)[:, None, None] * U
        ptr = jnp.where(ptr >= 0, ptr + base, -1.0)
        per_cfg = lambda a: jnp.swapaxes(
            a.reshape(ndev, S, k), 0, 1).reshape(S, cap)
        return DedupChunk(
            x=xs,
            y=col(0, U).reshape(ndev * U).astype(buf_dt),
            ids=col(U, 2 * U).reshape(ndev * U).astype(jnp.int32),
            ptr=jnp.swapaxes(ptr, 0, 1).reshape(S, cap).astype(jnp.int32),
            alpha=per_cfg(col(2 * U + S * k, 2 * U + 2 * S * k)
                          ).astype(buf_dt),
            mask=per_cfg(col(2 * U + 2 * S * k, 2 * U + 3 * S * k)
                         ).astype(buf_dt))
    per_cfg = lambda a: jnp.swapaxes(
        a.reshape(ndev, S, k), 0, 1).reshape(S, cap)
    col = lambda i: sides[:, i * S * k:(i + 1) * S * k]
    return SVBuffer(
        x=xs,
        y=per_cfg(col(0)).astype(buf_dt),
        alpha=per_cfg(col(1)).astype(buf_dt),
        ids=per_cfg(col(3)).astype(jnp.int32),
        mask=per_cfg(col(2)).astype(buf_dt))


def expand_sweep_sv(state, buf_dtype=jnp.float32) -> SVBuffer:
    """Materialize the per-config (S, cap, …) SVBuffer from a round
    state — identity for per-config states, one gather for the dedup
    state (its ``ptr`` is already on the global slot axis). The sharded
    driver calls this only when a config converges (to freeze its
    buffer) and once at the end — never on the per-round hot path."""
    if isinstance(state, DedupChunk):
        return expand_chunk(state, buf_dtype)
    if state.x.dtype != jnp.dtype(buf_dtype):
        return state._replace(x=state.x.astype(buf_dtype))
    return state


def make_sharded_sweep_round(cfg: MRSVMConfig, axis_names: Sequence[str],
                             num_devices: int, rows_per_device: int,
                             per_config_data: bool = False):
    """Per-device body solving S local subproblems per round.

    With ``cfg.shuffle_impl == "allgather"`` this wraps
    :func:`make_sharded_round`'s body in an inner ``vmap`` over the
    leading config axis of ``(sv, params)``; the shuffle becomes S
    all-gathers batched into one collective per buffer leaf. With
    ``"ring"`` or ``"hier"`` the transport is the packed,
    cross-config-deduplicated merge of :func:`_make_packed_sweep_body`
    over that transport's hop schedule. With ``per_config_data`` the
    rows/labels/mask also carry the (S,) job axis — S *streams* with
    distinct data updating in one device pass (the multi-tenant
    streaming wave, :mod:`repro.serving.svm_stream`).
    """
    axes = tuple(axis_names)
    if cfg.shuffle_impl in PACKED_SHUFFLES:
        return _make_packed_sweep_body(cfg, axes, num_devices,
                                       rows_per_device, per_config_data)
    body = make_sharded_round(cfg, axis_names, num_devices, rows_per_device)

    def sweep_body(Xl, yl, ml, sv_b: SVBuffer, params_b: SolverParams):
        if per_config_data:
            return jax.vmap(body)(Xl, yl, ml, sv_b, params_b)
        return jax.vmap(lambda sv, p: body(Xl, yl, ml, sv, p))(sv_b, params_b)

    return sweep_body


def sharded_sweep_program(mesh, data_axes: Sequence[str],
                          cfg: MRSVMConfig, rows_per_device: int,
                          per_config_data: bool = False):
    """shard_map-wrapped sweep round + its partition-spec contract.

    Single source of the sweep round's sharding: rows sharded over the
    data axes, SV buffers and params replicated with a leading (S,)
    config axis; with ``per_config_data`` the row inputs are
    ``(S, n, …)``, sharded on their SECOND axis. Returns
    ``(fn, in_specs, out_specs)`` — consumed by the jitted driver
    (:func:`build_sharded_sweep_round`) and the dry-run step builders
    (``launch.steps.build_svm_sweep_step`` /
    ``build_svm_serve_step``), so the program the dry-run validates is
    the program actually run.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(data_axes)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))
    body = make_sharded_sweep_round(cfg, axes, ndev, rows_per_device,
                                    per_config_data=per_config_data)
    row_spec = P(axes if len(axes) > 1 else axes[0])
    if per_config_data:
        data_spec = P(None, axes if len(axes) > 1 else axes[0])
        in_rows = (data_spec, data_spec, data_spec)
    else:
        in_rows = (row_spec, row_spec, row_spec)
    if uses_dedup_state(cfg, per_config_data):
        rep_buf = DedupChunk(*(P() for _ in DedupChunk._fields))
    else:
        rep_buf = SVBuffer(x=P(), y=P(), alpha=P(), ids=P(), mask=P())
    rep_par = SolverParams(*(P() for _ in SolverParams._fields))
    in_specs = in_rows + (rep_buf, rep_par)
    out_specs = (rep_buf, P(), P(), P())
    fn = compat.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
    return fn, in_specs, out_specs


def build_sharded_sweep_round(mesh, data_axes: Sequence[str],
                              cfg: MRSVMConfig, rows_per_device: int,
                              per_config_data: bool = False):
    """jit(shard_map(...)) one batched sweep round on ``mesh``.

    Returns ``f(X, y, mask, sv_b, params_b) -> (sv_b', risks (S, ndev),
    ws (S, d), bs (S,))`` where ``X`` is the GLOBAL array sharded on its
    leading axis (second axis when ``per_config_data``) and
    ``sv_b``/``params_b`` carry the replicated (S,) config axis — on
    the dedup ring, ``sv_b`` is the shared-row :class:`DedupChunk`
    state instead.

    The returned callable carries two helpers so drivers don't have to
    know which state layout the transport uses: ``.init_sv(S, d,
    dtype)`` builds the empty round-0 state and ``.expand_sv(state)``
    materializes the per-config (S, cap, …) :class:`SVBuffer` view.
    """
    axes = tuple(data_axes)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))
    fn, _, _ = sharded_sweep_program(mesh, data_axes, cfg, rows_per_device,
                                     per_config_data=per_config_data)
    jf = jax.jit(fn)

    def round_fn(X, y, mask, sv_b, params_b):
        return jf(X, y, mask, sv_b, params_b)

    round_fn.init_sv = lambda S, d, dtype=jnp.float32: init_sharded_sweep_sv(
        cfg, S, d, ndev, rows_per_device, dtype,
        per_config_data=per_config_data)
    round_fn.expand_sv = jax.jit(expand_sweep_sv) \
        if uses_dedup_state(cfg, per_config_data) else None
    return round_fn


class ShardedSweep(NamedTuple):
    """Host-driver output of :func:`run_sharded_sweep`."""
    risks: jax.Array    # (S,) best R_emp per config
    ws: jax.Array       # (S, d)
    bs: jax.Array       # (S,)
    sv: SVBuffer        # (S, cap, …)
    rounds: np.ndarray  # (S,)
    history: Tuple[dict, ...]

    @property
    def best(self) -> int:
        return int(np.argmin(np.asarray(self.risks)))


def run_sharded_sweep(round_fn, X: jax.Array, y: jax.Array,
                      mask: Optional[jax.Array], cfg: MRSVMConfig,
                      params: SolverParams,
                      verbose: bool = False,
                      fail_on_retrace: bool = False) -> ShardedSweep:
    """Host round loop over :func:`build_sharded_sweep_round` with the
    same per-config eq. 8 masking as :func:`fit_mapreduce_sweep`.
    When ``round_fn`` was built with ``per_config_data``, pass
    ``X (S, n, d)`` / ``y (S, n)`` / ``mask (S, n)``.

    On the dedup ring, ``round_fn`` threads the shared-row state and
    the driver snapshots per-config buffers only at convergence (see
    :func:`_run_rounds`); the returned :class:`ShardedSweep` always
    carries the standard (S, cap, …) :class:`SVBuffer`."""
    n, d = X.shape[-2], X.shape[-1]
    S = _num_configs(params)
    if mask is None:
        mask = jnp.ones(((S, n) if X.ndim == 3 else (n,)), X.dtype)
    init = getattr(round_fn, "init_sv", None)
    if init is not None:
        svb = init(S, d, X.dtype)
    else:
        sv0 = init_sv_buffer(cfg.sv_capacity, d, X.dtype)
        svb = compat.tree_map(
            lambda a: jnp.broadcast_to(a, (S,) + a.shape), sv0)
    snapshot = getattr(round_fn, "expand_sv", None)

    def step(sv_b, eff):
        sv_new, risks, ws, bs = round_fn(X, y, mask, sv_b, eff)
        # (ws, bs) are already the per-config best-reducer picks.
        with allowed_host_sync("per-reducer risk readback"):
            risks = np.asarray(risks)
        return sv_new, risks.min(axis=1), ws, bs

    svb, best_risk, best_w, best_b, rounds, history = _run_rounds(
        step, svb, d, cfg, params, verbose, "sharded-sweep",
        snapshot=snapshot, fail_on_retrace=fail_on_retrace)
    return ShardedSweep(risks=jnp.asarray(best_risk), ws=jnp.asarray(best_w),
                        bs=jnp.asarray(best_b), sv=svb, rounds=rounds,
                        history=history)


# ---------------------------------------------------------------------------
# Round-state ser/de (ISSUE 7) — the sweep's fault-tolerance hooks.
# ---------------------------------------------------------------------------

def save_sweep_state(path: str, state, step: Optional[int] = None) -> None:
    """Durably snapshot a sharded-sweep round state.

    ``state`` is whatever the transport threads between rounds — the
    per-config ``(S, cap, …)`` :class:`SVBuffer` on allgather, or the
    shared-row :class:`DedupChunk` on the dedup ring. Both are
    registered pytrees of array leaves, so the flat-npz checkpointer
    (:mod:`repro.ckpt.checkpoint`) takes them as-is; with ``step`` the
    directory's meta pointer advances atomically (crash-safe).
    """
    from repro.ckpt import checkpoint as ckpt
    ckpt.save(path, state, step=step)


def restore_sweep_state(path: str, cfg: MRSVMConfig, num_configs: int,
                        d: int, num_devices: int, rows_per_device: int,
                        dtype=jnp.float32, per_config_data: bool = False):
    """Restore a round state saved by :func:`save_sweep_state`.

    The ``like`` tree is rebuilt by :func:`init_sharded_sweep_sv` from
    the SAME static facts that shaped the original, so shape or dtype
    drift — a different sweep width, capacity, transport layout or wire
    dtype — fails loudly instead of resuming a subtly wrong sweep.
    """
    from repro.ckpt import checkpoint as ckpt
    like = init_sharded_sweep_sv(cfg, num_configs, d, num_devices,
                                 rows_per_device, dtype,
                                 per_config_data=per_config_data)
    return ckpt.restore(path, like)
