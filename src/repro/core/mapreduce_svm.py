"""The paper's contribution: iterative MapReduce SVM with global
support-vector exchange (Çatak 2014, Tablo 1-2, eq. 6-9).

Algorithm (one *round* = one MapReduce job):

  map    : D_l^t ← D_l ∪ SV_global^t          (augment partitions)
  reduce : (SV_l, h_l^t) ← binarySvm(D_l^t)   (local dual solve)
  merge  : SV_global^{t+1} ← ∪_l SV_l          (the "shuffle")
  driver : h^t = argmin_l R_emp(h_l^t);  stop when
           |R_emp(h^{t-1}) − R_emp(h^t)| ≤ γ  (eq. 8)

TPU-native adaptations (see DESIGN.md §2):

* XLA needs static shapes, so SV_global is a **capacity-bounded,
  mask-padded buffer**. Each partition contributes its top
  ``capacity // L`` support vectors by α — a balanced union.
* A row's "is a support vector" evidence is ``max(α_home, α_copy)``
  over every copy of the row (its home partition + the appended
  global-SV copies on all other partitions), matching the paper's
  set-union semantics without duplicate rows.
* Two execution modes share the same math:
  - **functional** (`fit_mapreduce`): partitions on the leading axis,
    reducers run under `vmap`. Used by tests, benchmarks, examples.
  - **sharded** (`make_sharded_round`): partitions = devices of the
    ``("data",)`` / ``("pod", "data")`` mesh axes under `shard_map`;
    the merge — the ICI analogue of the Hadoop shuffle — is a tiled
    `lax.all_gather`, the ring-pipelined `ppermute` transport, or the
    topology-aware two-level hier transport (``MRSVMConfig.
    shuffle_impl``, DESIGN.md §10/§16). Used by the launcher and the
    multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro import faults
from repro import sparse as sparse_rows
from repro.analysis.hostsync import allowed_host_sync
from repro.core import risk as risk_lib
from repro.core.svm import (BinarySVM, SolverParams, SVMConfig,
                            decision_kernel, decision_linear, fit_binary)

# Single source of truth for the merge-collective transports of the
# sharded mode (DESIGN.md §10, §16) and the eq. 8 convergence-readback
# collectives (§16). Config validation, ``configs/svm_tfidf.py``, the
# ``--shuffle`` CLI choices and the lint matrix all derive from these
# tuples, so a new transport cannot silently miss a layer.
SHUFFLE_IMPLS = ("allgather", "ring", "hier")
CONVERGE_IMPLS = ("psum", "tree")

# The transports whose wire format is the coalesced packed f32 message
# (ring stages or hier host-stages) — they share the hop engine
# (:func:`_merge_hops`) and, on sweeps, the dedup state layout.
PACKED_SHUFFLES = ("ring", "hier")


class SVBuffer(NamedTuple):
    """Capacity-bounded global support-vector set SV_global^t."""
    x: jax.Array      # (cap, d) feature rows
    y: jax.Array      # (cap,)   labels in {-1, +1} (0 on padding)
    alpha: jax.Array  # (cap,)   dual coefficient evidence (max over copies)
    ids: jax.Array    # (cap,)   stable global row ids (int32, -1 padding)
    mask: jax.Array   # (cap,)   1.0 where the slot holds a real SV


class RoundResult(NamedTuple):
    sv: SVBuffer
    risks: jax.Array   # (L,) empirical risk of every reducer hypothesis on FULL data
    ws: jax.Array      # (L, d) reducer primal hypotheses (linear path)
    bs: jax.Array      # (L,)
    sv_count: jax.Array  # () live slots in the new buffer


@dataclasses.dataclass(frozen=True)
class MRSVMConfig:
    """Driver configuration for the iterative MapReduce SVM.

    ``shuffle_impl`` selects the merge-collective transport of the
    sharded mode (DESIGN.md §10):

    * ``"allgather"`` — one blocking tiled ``all_gather`` of the full
      candidate buffer (the historical transport);
    * ``"ring"`` — the merge is split into ``num_devices`` ring stages
      over ``ppermute``, double-buffered so stage t's permute is in
      flight while stage t-1's chunk is consumed (buffer assembly +
      eq. 7 hypothesis scoring overlap the collective), with feature
      rows shipped in ``shuffle_wire_dtype`` (f32 α/ids sideband);
    * ``"hier"`` — the topology-aware two-level transport (§16): the
      flat ring's ``num_devices`` stages collapse to ``num_hosts``
      host-stages — per stage ONE inter-host ``ppermute`` (each device
      forwards its slice of the in-flight host super-message, so only
      the bytes a host has never seen cross the network) expanded by an
      intra-host grouped ``all_gather`` (fast local interconnect) into
      the arrived host's messages, still overlapping eq. 7 scoring.
      ``hier_num_hosts`` pins the host-group count for simulated
      topologies; ``None`` reads the real process count at build time.

    All transports converge to the same model; the packed transports
    (ring, hier) additionally dedup cross-config SV rows on the sweep
    axis (``sweep_dedup``, :mod:`repro.core.sweep`):
    ``dedup_max_unique`` caps the unique-row slots a device ships per
    round — ``None`` means ``min(S·k, per)``, which can never drop a
    live row (lossless) while shrinking the S× payload whenever configs
    share rows or ``per < S·k``.

    ``converge_impl`` selects the eq. 8 convergence-readback collective
    (the global risk mean): ``"psum"`` is the flat all-reduce,
    ``"tree"`` the log2(P) recursive-doubling (binomial-tree) exchange
    over XOR-partner ``ppermute`` stages (power-of-two device counts).
    """
    sv_capacity: int = 256
    svm: SVMConfig = SVMConfig()
    gamma: float = 1e-3          # eq. 8 convergence tolerance on R_emp
    max_rounds: int = 10
    risk_loss: str = "hinge"     # 'hinge' (used in eq. 6) or 'zero_one'
    shuffle_impl: str = "allgather"       # one of SHUFFLE_IMPLS
    shuffle_wire_dtype: str = "bfloat16"  # packed: feature-row wire dtype
    sweep_dedup: bool = True              # packed sweep: cross-config dedup
    dedup_max_unique: Optional[int] = None  # unique slots/chunk; None=lossless
    hier_num_hosts: Optional[int] = None  # hier: host groups; None=processes
    converge_impl: str = "psum"           # one of CONVERGE_IMPLS
    # Ring wire-integrity check (DESIGN.md §15): each hop's coalesced
    # message carries one extra f32 lane holding the int32 wrap-sum of
    # its bitcast payload; a receiver-side mismatch poisons the round's
    # risks to +inf, which the host driver turns into a typed
    # FaultDetected at its eq. 8 readback. Off by default — the lane
    # changes the compiled program, and the committed dry-run artifacts
    # record the unchecked transport.
    shuffle_wire_check: bool = False

    def __post_init__(self):
        if self.shuffle_impl not in SHUFFLE_IMPLS:
            raise ValueError(
                f"shuffle_impl must be one of {SHUFFLE_IMPLS}, "
                f"got {self.shuffle_impl!r}")
        if self.converge_impl not in CONVERGE_IMPLS:
            raise ValueError(
                f"converge_impl must be one of {CONVERGE_IMPLS}, "
                f"got {self.converge_impl!r}")
        if self.hier_num_hosts is not None and self.hier_num_hosts < 1:
            raise ValueError(
                f"hier_num_hosts must be >= 1, got {self.hier_num_hosts}")
        wdt = jnp.dtype(self.shuffle_wire_dtype)
        if wdt.itemsize not in (2, 4) or \
                not jnp.issubdtype(wdt, jnp.floating):
            raise ValueError(
                "shuffle_wire_dtype must be a 2- or 4-byte float "
                f"(bf16/f16/f32), got {self.shuffle_wire_dtype!r}")


def init_sv_buffer(capacity: int, d: int, dtype=jnp.float32,
                   nnz_cap: Optional[int] = None) -> SVBuffer:
    """SV_global^0 = ∅ (empty, mask-padded buffer). With ``nnz_cap``
    the feature rows are blocked-CSR :class:`repro.sparse.SparseRows`
    (index 0 / value 0 padding ≡ the empty row)."""
    if nnz_cap is None:
        x = jnp.zeros((capacity, d), dtype)
    else:
        x = sparse_rows.SparseRows(
            jnp.zeros((capacity, nnz_cap), jnp.int32),
            jnp.zeros((capacity, nnz_cap), dtype), d)
    return SVBuffer(
        x=x,
        y=jnp.zeros((capacity,), dtype),
        alpha=jnp.zeros((capacity,), dtype),
        ids=-jnp.ones((capacity,), jnp.int32),
        mask=jnp.zeros((capacity,), dtype),
    )


def _augment(Xl, yl, ml, sv: SVBuffer):
    """map phase: D_l ← D_l ∪ SV_global (per partition)."""
    Xa = sparse_rows.rows_concat(Xl, sv.x, axis=0)
    ya = jnp.concatenate([yl, sv.y], axis=0)
    ma = jnp.concatenate([ml, sv.mask], axis=0)
    return Xa, ya, ma


def _fit_union(Xl, yl, ml, sv: SVBuffer, svm_cfg: SVMConfig,
               params: Optional[SolverParams], vma_axes: tuple = ()):
    """reduce phase on D_l ∪ SV_global. The SV rows are passed as the
    solver's ``tail`` block rather than concatenated: under the
    partition vmap a concatenation broadcasts the shared buffer to
    every partition and copies the union, (L, per + cap, d) twice."""
    ya = jnp.concatenate([yl, sv.y], axis=0)
    ma = jnp.concatenate([ml, sv.mask], axis=0)
    return fit_binary(Xl, ya, ma, svm_cfg, params=params,
                      vma_axes=vma_axes, tail=sv.x)


# ---------------------------------------------------------------------------
# Functional (vmap) mode — partitions on a leading axis.
# ---------------------------------------------------------------------------

def mapreduce_round(Xp: jax.Array, yp: jax.Array, maskp: jax.Array,
                    sv: SVBuffer, cfg: MRSVMConfig,
                    params: Optional[SolverParams] = None) -> RoundResult:
    """One full MapReduce round over stacked partitions.

    Xp: (L, per, d); rows are ordered so global id of (l, i) = l*per + i.
    ``params`` optionally overrides the value-like solver hyper-params
    with a traced pytree — the hook the sweep subsystem vmaps over.
    """
    L, per, d = Xp.shape
    p = cfg.svm.params() if params is None else params
    cap = sv.x.shape[0]
    if cap % L != 0:
        raise ValueError(f"sv_capacity {cap} must divide by partitions {L}")
    k = cap // L

    # --- map + reduce ------------------------------------------------------
    # NB: forward the *original* ``params`` (possibly None), not the
    # lifted ``p`` — fit_binary distinguishes "no override" (static
    # defaults, Pallas Gram allowed) from a traced sweep override.
    def reducer(Xl, yl, ml):
        return _fit_union(Xl, yl, ml, sv, cfg.svm, params)

    res: BinarySVM = jax.vmap(reducer)(Xp, yp, maskp)
    alpha = res.alpha                                # (L, per + cap)

    with jax.named_scope("mr.merge"):
        home_alpha = alpha[:, :per].reshape(-1)      # (L*per,) by global id
        copy_alpha = alpha[:, per:]                  # (L, cap) appended copies

        # --- union semantics: α_eff(row) = max over all copies --------------
        buf_alpha = jnp.max(copy_alpha, axis=0) * sv.mask          # (cap,)
        safe_ids = jnp.where(sv.ids >= 0, sv.ids, 0)
        folded = jnp.zeros_like(home_alpha).at[safe_ids].max(
            jnp.where(sv.ids >= 0, buf_alpha, 0.0))
        home_alpha = jnp.maximum(home_alpha, folded).reshape(L, per) * maskp

        # --- merge: balanced top-k per partition, concatenated ---------------
        topv, topi = jax.lax.top_k(home_alpha, k)                   # (L, k)
        sel = lambda A: jnp.take_along_axis(A, topi, axis=1)
        new_x = sparse_rows.take_rows_along(Xp, topi).reshape(cap, d)
        new_y = sel(yp).reshape(cap)
        live = (topv > p.sv_threshold).astype(Xp.dtype)
        base_ids = ((jnp.arange(L, dtype=jnp.int32) * per)[:, None]
                    + topi.astype(jnp.int32))
        new_sv = SVBuffer(
            x=new_x * live.reshape(cap, 1),
            y=new_y * live.reshape(cap),
            alpha=(topv * live).reshape(cap),
            ids=jnp.where(live.reshape(cap) > 0, base_ids.reshape(cap), -1),
            mask=live.reshape(cap),
        )

    # --- driver: risk of every reducer hypothesis on the FULL data (eq. 7) --
    with jax.named_scope("mr.score"):
        Xflat = Xp.reshape(L * per, d)
        yflat = yp.reshape(L * per)
        mflat = maskp.reshape(L * per)
        if cfg.svm.kernel.name == "linear" and not cfg.svm.use_gram:
            scores = Xflat @ res.w.T + res.b[None, :]               # (n, L)
            risks = jax.vmap(
                lambda s: risk_lib.empirical_risk(s, yflat, mflat,
                                                  cfg.risk_loss),
                in_axes=1)(scores)
        else:
            def risk_of(Xa, ya, ma, a, b):
                coef = a * ya * ma
                s = decision_kernel(Xa, coef, b, Xflat, cfg.svm.kernel,
                                    gamma=p.gamma, coef0=p.coef0)
                return risk_lib.empirical_risk(s, yflat, mflat,
                                               cfg.risk_loss)
            Xa, ya, ma = jax.vmap(lambda X, y, m: _augment(X, y, m, sv))(
                Xp, yp, maskp)
            risks = jax.vmap(risk_of)(Xa, ya, ma, alpha, res.b)
    return RoundResult(sv=new_sv, risks=risks, ws=res.w, bs=res.b,
                       sv_count=jnp.sum(new_sv.mask))


# Module-level jits keyed on the (hashable, frozen) cfg: repeated
# fit_mapreduce / update_mapreduce calls with the same shapes+config hit
# the jit cache instead of retracing per call. A per-call
# ``jax.jit(lambda ...)`` would recompile EVERY streaming wave — the
# trace cost then dwarfs the (new rows ∪ SVs) compute advantage the
# incremental update exists for (benchmarks/streaming.py).
@functools.partial(jax.jit, static_argnames=("cfg",))
def _round_jit(Xp, yp, maskp, sv, params, cfg: MRSVMConfig) -> RoundResult:
    return mapreduce_round(Xp, yp, maskp, sv, cfg, params=params)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _final_fit_jit(sv: SVBuffer, params, cfg: MRSVMConfig) -> BinarySVM:
    return fit_binary(sv.x, sv.y, sv.mask, cfg.svm, params=params)


class MapReduceSVM(NamedTuple):
    """Driver output: best reducer hypothesis (eq. 7) + final SV model."""
    w: jax.Array            # (d,) best linear hypothesis (zeros on kernel path)
    b: jax.Array
    sv: SVBuffer            # converged SV_global
    final: BinarySVM        # model retrained on SV_global alone
    risk: jax.Array         # R_emp(h^T) of the selected hypothesis
    rounds: int
    history: Tuple[dict, ...]


def fit_mapreduce(X: jax.Array, y: jax.Array, num_partitions: int,
                  cfg: MRSVMConfig,
                  mask: Optional[jax.Array] = None,
                  params: Optional[SolverParams] = None,
                  verbose: bool = False) -> MapReduceSVM:
    """Iterative MapReduce SVM driver (functional mode).

    Pads ``X`` to a multiple of ``num_partitions`` and loops rounds on
    the host until eq. 8 fires or ``max_rounds`` is hit. ``params``
    optionally overrides the value-like solver hyper-params (traced).
    """
    with jax.profiler.TraceAnnotation("mr.fit"):
        n, d = X.shape
        L = num_partitions
        per = -(-n // L)
        pad = L * per - n
        Xp = sparse_rows.pad_rows(X, pad).reshape(L, per, d)
        yp = jnp.pad(y.astype(X.dtype), (0, pad)).reshape(L, per)
        base_mask = (jnp.ones((n,), X.dtype) if mask is None
                     else mask.astype(X.dtype))
        maskp = jnp.pad(base_mask, (0, pad)).reshape(L, per)

        sv = init_sv_buffer(
            cfg.sv_capacity, d, X.dtype,
            nnz_cap=X.nnz_cap if sparse_rows.is_sparse(X) else None)

        best = (np.inf, None, None)
        prev_risk = np.inf
        history = []
        rounds_done = 0
        for t in range(cfg.max_rounds):
            with jax.profiler.TraceAnnotation("mr.round", round=t):
                # transport seams (DESIGN.md §15): a delayed round
                # completes late but EXACTLY (survived bit-for-bit); a
                # transiently failing merge is retried with backoff —
                # only the injected TransientFault retries, real solver
                # errors surface at once.
                faults.maybe_sleep("transport.round", when=t)

                def run_round():
                    faults.maybe_raise("transport.merge",
                                       kinds=("transport_exc",), when=t)
                    return _round_jit(Xp, yp, maskp, sv, params, cfg=cfg)

                out = faults.retry_with_backoff(
                    run_round, attempts=3, base_s=0.05,
                    retry_on=faults.TransientFault, layer="transport",
                    cause=f"merge collective at round {t}",
                    action="check inter-host links; a persistent failure "
                           "means the mesh lost a member — restart from "
                           "the last checkpoint")
                sv = out.sv
                # eq. 8's designed device→host sync point, the round's
                # only one: sanctioned for the host-sync lint (DESIGN.md
                # §14) by name, right where it happens.
                with allowed_host_sync("eq. 8 risk readback"), \
                        jax.profiler.TraceAnnotation("mr.eq8", round=t):
                    risks, sv_count = jax.device_get(
                        (out.risks, out.sv_count))
                faults.check_finite_risks(risks, where=f"mapreduce round {t}")
                l_star = int(np.argmin(risks))
                r_star = float(risks[l_star])
                if r_star < best[0]:
                    best = (r_star, out.ws[l_star], out.bs[l_star])
                history.append({"round": t, "risk": r_star, "reducer": l_star,
                                "sv_count": int(sv_count)})
                rounds_done = t + 1
                if verbose:
                    print(f"[mapreduce-svm] round={t} R_emp={r_star:.5f} "
                          f"|SV|={int(sv_count)}")
                if t > 0 and abs(prev_risk - r_star) <= cfg.gamma:   # eq. 8
                    break
                prev_risk = r_star

        # Final consolidated model: retrain on SV_global alone
        # (cascade-style).
        with jax.profiler.TraceAnnotation("mr.final"):
            final = _final_fit_jit(sv, params, cfg=cfg)
        return MapReduceSVM(w=best[1], b=best[2], sv=sv, final=final,
                            risk=jnp.asarray(best[0]), rounds=rounds_done,
                            history=tuple(history))


def predict(model: MapReduceSVM, X: jax.Array, cfg: MRSVMConfig,
            use_final: bool = True,
            params: Optional[SolverParams] = None) -> jax.Array:
    """±1 predictions from the converged model. Pass the same ``params``
    the model was trained with (if any) so kernel scales match."""
    if cfg.svm.kernel.name == "linear" and not cfg.svm.use_gram:
        w, b = (model.final.w, model.final.b) if use_final else (model.w, model.b)
        return jnp.where(decision_linear(w, b, X) >= 0, 1.0, -1.0)
    s = decision_values(model, X, cfg, params=params)
    return jnp.where(s >= 0, 1.0, -1.0)


def decision_values(model: MapReduceSVM, X: jax.Array,
                    cfg: MRSVMConfig,
                    params: Optional[SolverParams] = None) -> jax.Array:
    if cfg.svm.kernel.name == "linear" and not cfg.svm.use_gram:
        return decision_linear(model.final.w, model.final.b, X)
    coef = model.final.alpha * model.sv.y * model.sv.mask
    gamma = None if params is None else params.gamma
    coef0 = None if params is None else params.coef0
    return decision_kernel(model.sv.x, coef, model.final.b, X,
                           cfg.svm.kernel, gamma=gamma, coef0=coef0)


def update_mapreduce(model: MapReduceSVM, X_new: jax.Array,
                     y_new: jax.Array, num_partitions: int,
                     cfg: MRSVMConfig,
                     params: Optional[SolverParams] = None,
                     verbose: bool = False) -> MapReduceSVM:
    """Incremental model update — the paper's stated future work
    (§SONUÇ: "zaman içerisinde kendini güncelleyen eğitim veri seti
    kullanılarak sınıflandırma modelinin güncelliğini koruması").

    The converged global SV set is the model's sufficient statistic:
    updating on a new message batch trains on (new data ∪ old SVs) —
    old non-support examples never travel, the same bandwidth argument
    as the original shuffle. Returns a fresh converged model.

    Pass the same ``params`` the model was trained with (if any): the
    carried SV alphas were solved at that kernel scale, so re-fitting
    with the config defaults would silently change gamma/coef0/C under
    a sweep-trained model.
    """
    d_model = model.sv.x.shape[1]
    if X_new.shape[1] != d_model:
        raise ValueError(
            f"update batch has {X_new.shape[1]} features but the model's "
            f"SV buffer holds {d_model}-dim rows — vectorize new messages "
            "with the SAME featurizer (hash space / idf) as training")
    X = sparse_rows.rows_concat(X_new, model.sv.x, axis=0)
    y = jnp.concatenate([y_new.astype(X_new.dtype), model.sv.y], axis=0)
    mask = jnp.concatenate([jnp.ones((X_new.shape[0],), X_new.dtype),
                            model.sv.mask], axis=0)
    return fit_mapreduce(X, y, num_partitions, cfg, mask=mask,
                         params=params, verbose=verbose)


# ---------------------------------------------------------------------------
# Sharded (shard_map) mode — partitions = devices.
# ---------------------------------------------------------------------------

def _round_candidates(Xl, yl, ml, sv: SVBuffer, cfg: MRSVMConfig,
                      axes, idx, k: int, per: int,
                      params: Optional[SolverParams]):
    """map + reduce + union-fold + balanced top-k of ONE device.

    Returns ``(cand, w, b)``: the device's (k,)-row candidate SV chunk
    and its reducer hypothesis. Shared by both merge transports and
    vmapped over the config axis by the sweep subsystem.
    """
    p = cfg.svm.params() if params is None else params
    # map + reduce (original ``params``, not ``p`` — see mapreduce_round)
    res = _fit_union(Xl, yl, ml, sv, cfg.svm, params, vma_axes=axes)
    with jax.named_scope("mr.merge"):
        home_alpha = res.alpha[:per]
        copy_alpha = res.alpha[per:] * sv.mask

        # union semantics: fold the max appended-copy α back into the
        # home rows (buffer row with global id g lives on device g//per).
        buf_alpha = compat.pmax(copy_alpha, axes)           # (cap,)
        mine = jnp.logical_and(sv.ids >= 0, sv.ids // per == idx)
        pos = jnp.where(mine, sv.ids % per, 0)
        folded = jnp.zeros((per,), home_alpha.dtype).at[pos].max(
            jnp.where(mine, buf_alpha, 0.0))
        home_alpha = jnp.maximum(home_alpha, folded) * ml

        # balanced top-k per device — the candidate chunk of the shuffle
        topv, topi = jax.lax.top_k(home_alpha, k)
        live = (topv > p.sv_threshold).astype(Xl.dtype)
        cand_ids = (idx * per + topi).astype(jnp.int32)
        cand = SVBuffer(
            x=Xl[topi] * live[:, None],
            y=yl[topi] * live,
            alpha=topv * live,
            ids=jnp.where(live > 0, cand_ids, -1),
            mask=live,
        )
    return cand, res.w, res.b


def _device_risks(scores, yl, ml, cfg: MRSVMConfig, axes, ndev: int):
    """eq. 7 empirical risks from per-device (per, ndev) scores.

    The global (Σ loss)/(Σ count) is the eq. 8 convergence-readback
    collective: ``converge_impl="psum"`` is the flat all-reduce;
    ``"tree"`` runs log2(ndev) recursive-doubling (binomial-tree)
    stages over XOR-partner ``ppermute``s — partial risks and the row
    count ride ONE combined vector, so each stage is a single wire
    message and the reduction finishes in log2(ndev) hops instead of
    the flat all-reduce's implementation-chosen schedule (§16).
    """
    with jax.named_scope("mr.score"):
        if cfg.risk_loss == "hinge":
            per_ex = jnp.maximum(0.0, 1.0 - yl[:, None] * scores)
        else:
            # Shared decision convention (score >= 0 → +1) with
            # risk_lib.zero_one_loss / predict — see that docstring.
            per_ex = risk_lib.zero_one_loss(scores, yl[:, None]).astype(
                scores.dtype)
        part = jnp.sum(per_ex * ml[:, None], axis=0)
        cnt = jnp.sum(ml)
        if cfg.converge_impl == "tree":
            vec = jnp.concatenate([part, cnt.reshape(1).astype(part.dtype)])
            s = 1
            while s < ndev:              # power of two — build-time checked
                vec = vec + compat.ppermute(
                    vec, axes, [(i, i ^ s) for i in range(ndev)])
                s <<= 1
            return vec[:-1] / jnp.maximum(vec[-1], 1.0)
        return compat.psum(part, axes) / jnp.maximum(
            compat.psum(cnt, axes), 1.0)


def _pack_lanes(xw, wire_dt):
    """(n, m) wire-dtype matrix → ``(lanes (n, slots) f32, slots)``:
    2-byte dtypes bitcast element PAIRS into one f32 lane (lossless —
    the bits just ride along), 4-byte floats pass through."""
    n, m = xw.shape
    size = jnp.dtype(wire_dt).itemsize
    if size == 2:
        mp = m + (m % 2)
        xw = jnp.pad(xw, ((0, 0), (0, mp - m)))
        return jax.lax.bitcast_convert_type(
            xw.reshape(n, mp // 2, 2), jnp.float32), mp // 2
    if size != 4:
        raise ValueError(f"unsupported shuffle_wire_dtype {wire_dt}")
    return jax.lax.bitcast_convert_type(xw, jnp.float32), m


def _unpack_lanes(lanes, m: int, wire_dt):
    """Inverse of :func:`_pack_lanes`: (n, slots) f32 → (n, m) wire."""
    n = lanes.shape[0]
    if jnp.dtype(wire_dt).itemsize == 2:
        rows = jax.lax.bitcast_convert_type(lanes, wire_dt)  # (n, slots, 2)
        return rows.reshape(n, -1)[:, :m]
    return jax.lax.bitcast_convert_type(lanes, wire_dt)


def pack_wire_rows(x, wire_dt):
    """Flatten feature rows into f32 lanes for the coalesced ring
    message. Returns ``(flat, wslots)`` with ``wslots`` f32 lanes per
    row.

    Dense rows ship all ``d`` features in the wire dtype. Blocked-CSR
    rows (:class:`repro.sparse.SparseRows`) ship per row only the
    ``nnz_cap`` (index, value) pairs — values packed like the dense
    case, int32 indices bitcast into f32 lanes verbatim (never
    quantized) — so the payload scales with ``nnz_cap``, not ``d``:
    the ~10-100× shrink on top of the bf16 pair-packing (DESIGN.md
    §12)."""
    if sparse_rows.is_sparse(x):
        vf, vslots = _pack_lanes(x.values.astype(jnp.dtype(wire_dt)),
                                 wire_dt)
        idxf = jax.lax.bitcast_convert_type(x.indices, jnp.float32)
        lanes = jnp.concatenate([vf, idxf], axis=1)
        return lanes.reshape(-1), vslots + x.nnz_cap
    n, d = x.shape
    lanes, slots = _pack_lanes(x.astype(jnp.dtype(wire_dt)), wire_dt)
    return lanes.reshape(n * slots), slots


def unpack_wire_rows(flat, n: int, d: int, wire_dt, wslots: int,
                     nnz_cap: Optional[int] = None):
    """Inverse of :func:`pack_wire_rows`: f32 lanes → (n, d) wire-dtype
    feature rows (dense), or — with ``nnz_cap`` — the blocked-CSR
    :class:`repro.sparse.SparseRows` the sparse pack shipped."""
    wire_dt = jnp.dtype(wire_dt)
    arr = flat.reshape(n, wslots)
    if nnz_cap is not None:
        vslots = wslots - nnz_cap
        vals = _unpack_lanes(arr[:, :vslots], nnz_cap, wire_dt)
        idx = jax.lax.bitcast_convert_type(arr[:, vslots:], jnp.int32)
        return sparse_rows.SparseRows(idx, vals, d)
    return _unpack_lanes(arr, d, wire_dt)


class _HopPlan(NamedTuple):
    """Transport parameterization of the hop engine (:func:`_merge_hops`):
    ``num_stages`` hops of the ``shift`` permutation, each expanded by
    the ``expand`` group collective into ``m`` arrived messages; ``gi``
    is this device's (traced) origin-group index for the assembly roll.
    """
    num_stages: int   # hops of the merge (ring: ndev, hier: num_hosts)
    m: int            # messages consumed per stage (ring: 1, hier: ndev/H)
    gi: jax.Array     # this device's origin-group index (traced)
    shift: object     # hop permutation: in-flight (L,) msg -> next group
    expand: object    # group collective: (L,) msg -> (m, L) arrived block


def resolve_topology(cfg: MRSVMConfig, num_devices: int) -> int:
    """Build-time topology facts: the hier host-group count, plus the
    static validation the collectives need.

    ``cfg.hier_num_hosts`` pins the host count (simulated topologies,
    dry-runs); ``None`` reads the real process count — the process-major
    device order of ``launch.mesh.make_cluster_mesh`` guarantees
    host = flat_index // local_device_count, which is exactly the
    grouping the hier plan's groups/permutation assume. One host
    degenerates to a single grouped all_gather (zero inter-host hops);
    hosts == num_devices degenerates to the flat ring.
    """
    if cfg.converge_impl == "tree" and (num_devices & (num_devices - 1)):
        raise ValueError(
            "converge_impl='tree' (recursive doubling) needs a "
            f"power-of-two device count, got {num_devices}")
    if cfg.shuffle_impl != "hier":
        return 1
    hosts = cfg.hier_num_hosts or max(compat.process_count(), 1)
    if num_devices % hosts:
        raise ValueError(
            f"hier shuffle needs the device count ({num_devices}) "
            f"divisible by the host count ({hosts}); pin "
            "MRSVMConfig.hier_num_hosts for simulated topologies")
    return hosts


def _hop_plan(cfg: MRSVMConfig, axes, ndev: int, idx,
              hosts: int) -> _HopPlan:
    """The (group collective, hop permutation, messages-per-hop) triple
    of each packed transport (DESIGN.md §16).

    * ``ring``: ndev stages of the flattened-ring shift, one message
      per stage, no group collective (``expand`` is a reshape).
    * ``hier``: ``hosts`` host-stages. Device (h, l) = flat h·Dl+l
      forwards its (L,)-slice of the in-flight host super-message to
      device (h+1, l) — a FULL permutation whose every pair crosses a
      host boundary, so per stage exactly Dl·L values (the bytes the
      next host has never seen — the information floor) cross the
      network. The intra-host grouped all_gather then reassembles the
      arrived host's Dl messages on the local interconnect for scoring
      and assembly. The ppermute chain forwards the cp INPUT, not the
      gather output, so stage t+1's wire time overlaps stage t's
      expand+consume exactly like the flat ring's double buffering.
    """
    if cfg.shuffle_impl == "ring":
        return _HopPlan(
            num_stages=ndev, m=1, gi=idx,
            shift=lambda c: compat.ring_shift(c, axes),
            expand=lambda c: c[None, :])
    Dl = ndev // hosts
    groups = [[h * Dl + l for l in range(Dl)] for h in range(hosts)]
    perm = [(h * Dl + l, ((h + 1) % hosts) * Dl + l)
            for h in range(hosts) for l in range(Dl)]
    return _HopPlan(
        num_stages=hosts, m=Dl, gi=idx // Dl,
        shift=lambda c: compat.ppermute(c, axes, perm),
        expand=lambda c: compat.all_gather_groups(c, axes, groups))


def _merge_hops(side, plan: _HopPlan, consume):
    """The transport-generic hop engine every packed transport shares
    (DESIGN.md §16): ``plan.num_stages`` iterations, each launching the
    NEXT stage's ``shift`` (the wire permutation) before expanding the
    current in-flight message with the ``expand`` group collective into
    the (m, L) block that ARRIVED this stage and handing it to
    ``consume`` (the overlapped eq. 7 work) — XLA's
    collective-permute-start/done pair brackets the stage's compute, so
    the wire time hides behind it. ``allgather`` is the degenerate
    num_stages=1, m=ndev parameterization of the same loop; the
    baseline transport realizes it per-leaf in exact dtype instead
    (see :func:`make_sharded_round`).

    Stage t carries origin group ``(gi - t) mod num_stages``, so the
    REVERSED arrival list is origin groups gi+1, gi+2, … (contiguous
    mod the group count) and ONE roll of ``(gi + 1)`` group blocks is
    the origin-device-order layout — a per-stage dynamic-update-slice
    chain would rewrite the whole buffer every hop, costing
    num_stages× the assembly traffic.

    Returns ``(M, ordered)``: the (ndev, L) device-order message
    matrix and the per-stage ``consume`` outputs concatenated into
    device order along their leading (m,) axis.
    """
    L = side.shape[0]
    msgs, parts = [], []
    cur = side
    for t in range(plan.num_stages):
        # faults.garble_wire is the trace-time chaos seam: a no-op
        # (bit-identical program) unless a ring_garble plan is armed
        # while this round is being BUILT.
        nxt = (faults.garble_wire(plan.shift(cur), hop=t)
               if t < plan.num_stages - 1 else None)
        blk = plan.expand(cur)                 # (m, L) arrived messages
        msgs.append(blk.reshape(plan.m * L))
        parts.append(consume(blk))             # eq. 7 stage
        cur = nxt
    ndev = plan.num_stages * plan.m
    M = jnp.roll(jnp.concatenate(msgs[::-1]),
                 (plan.gi + 1) * (plan.m * L)).reshape(ndev, L)
    ordered = jnp.roll(jnp.concatenate(parts[::-1], axis=0),
                       (plan.gi + 1) * plan.m, axis=0)
    return M, ordered


def _packed_merge(cand: SVBuffer, w, b, Xl, cfg: MRSVMConfig, axes,
                  ndev: int, k: int, hosts: int = 1):
    """Packed-wire merge + eq. 7 scoring (DESIGN.md §10, §16) — the
    ring and hier transports over the shared hop engine.

    The monolithic all_gather is split into hop-engine stages (ring:
    ``ndev`` single-message stages; hier: ``hosts`` host-stages of
    ``ndev // hosts`` messages): at each stage a device consumes the
    arrived origin chunks — writing them into the assembling buffer and
    scoring those origins' hypotheses on the local rows — while the
    permutation carrying the next stage's payload is already in flight.
    Feature rows travel in ``cfg.shuffle_wire_dtype`` (bf16 halves the
    dominant payload, matching the bf16-feature convention of
    :mod:`repro.core.svm`); α/ids/y/mask and the (w, b) hypotheses stay
    a full-precision sideband — solver state is never quantized.

    Every device applies the identical wire round-trip to every chunk
    (including its own), so the assembled buffer is bit-identical and
    replicated across devices, exactly like the all_gather's output.
    The buffer's feature rows STAY in the wire dtype — candidates are
    re-gathered from the local f32/bf16 rows every round, so rounding
    never compounds, and the next round's augment reads ½ the bytes.
    """
    per, d = Xl.shape
    wire_dt = jnp.dtype(cfg.shuffle_wire_dtype)
    f32 = jnp.float32
    nnzc = cand.x.nnz_cap if sparse_rows.is_sparse(cand.x) else None
    idx = compat.axis_index(axes)
    plan = _hop_plan(cfg, axes, ndev, idx, hosts)

    # ONE coalesced f32 message per hop: the wire-dtype feature rows
    # (bf16 pairs bitcast into f32 lanes) followed by the packed
    # sideband [y | α | mask | ids | w | b]. Per-leaf permutes would
    # pay the collective's fixed launch/rendezvous cost 7× per stage.
    # ids/int values are exact in f32 below 2^24 rows.
    xf, wslots = pack_wire_rows(cand.x, wire_dt)
    side = jnp.concatenate([
        xf, cand.y.astype(f32), cand.alpha.astype(f32),
        cand.mask.astype(f32), cand.ids.astype(f32),
        w.astype(f32), b.reshape(1).astype(f32)])
    o_x = k * wslots
    o_w = o_x + 4 * k
    if cfg.shuffle_wire_check:
        # Integrity lane (DESIGN.md §15): the int32 wrap-sum of the
        # bitcast message rides as one trailing f32 lane. Every slice
        # below addresses the message by offset from the front, so the
        # lane is invisible to assembly; the receiver re-sums each
        # arrived chunk after the roll.
        csum = jnp.sum(jax.lax.bitcast_convert_type(side, jnp.int32))
        side = jnp.concatenate(
            [side, jax.lax.bitcast_convert_type(csum.reshape(1), f32)])
    L = side.shape[0]

    def consume(blk):                  # (m, L) arrived → (m, per) scores
        Wt = blk[:, o_w:o_w + d]
        Bt = blk[:, o_w + d]
        return (Xl @ Wt.T + Bt[None, :]).astype(w.dtype).T

    M, ordered = _merge_hops(side, plan, consume)
    col = lambda a, b2: M[:, o_x + a * k:o_x + b2 * k].reshape(ndev * k)
    bt_ = Xl.dtype
    sv_acc = SVBuffer(
        x=unpack_wire_rows(M[:, :o_x], ndev * k, d, wire_dt, wslots,
                           nnz_cap=nnzc),
        y=col(0, 1).astype(bt_),
        alpha=col(1, 2).astype(bt_),
        ids=col(3, 4).astype(jnp.int32),
        mask=col(2, 3).astype(bt_))
    W = M[:, o_w:o_w + d]                            # (ndev, d)
    B = M[:, o_w + d]                                # (ndev,)
    scores = ordered.T                               # (per, ndev)
    if cfg.shuffle_wire_check:
        got = jax.lax.bitcast_convert_type(M[:, L - 1], jnp.int32)
        want = jnp.sum(
            jax.lax.bitcast_convert_type(M[:, :L - 1], jnp.int32), axis=1)
        wire_ok = jnp.all(got == want)
    else:
        wire_ok = None
    return sv_acc, W, B, scores, wire_ok


def make_sharded_round(cfg: MRSVMConfig, axis_names: Sequence[str],
                       num_devices: int, rows_per_device: int):
    """Build the per-device body of one MapReduce round for `shard_map`.

    The returned function runs on ONE device's shard:
      Xl (per, d), yl (per,), ml (per,), sv (replicated SVBuffer)
    and returns (new_sv, risks (ndev,), best_w (d,), best_b ()).

    The merge collective — the ICI analogue of the Hadoop shuffle — is
    selected by ``cfg.shuffle_impl``:

    * ``"allgather"``: one tiled `all_gather` of the candidate chunks
      over ``axis_names``; hypothesis selection (eq. 7) all-gathers the
      per-device (w, b) and psums partial risks afterwards — reducer-
      side compute waits on the full collective. This is the hop
      engine's degenerate num_stages=1, m=ndev parameterization,
      realized per-leaf in exact dtype (no wire pack) so the baseline
      stays the bit-exact f32 oracle.
    * ``"ring"``: :func:`_packed_merge` — the chunk exchange is
      pipelined into ``num_devices`` `ppermute` stages, double-buffered
      so buffer assembly and the eq. 7 scoring of each arrived
      hypothesis overlap the next stage's wire time, with feature rows
      shipped in ``cfg.shuffle_wire_dtype``.
    * ``"hier"``: :func:`_packed_merge` over the two-level hop plan —
      ``num_hosts`` host-stages (one inter-host slice permutation +
      one intra-host grouped all_gather each), so only
      (hosts−1)·ndev·L values ever cross the network: the information
      floor, vs the flat ring's hosts·(ndev−1)·L (DESIGN.md §16).

    All transports produce the same converged model (the packed
    transports are bit-identical up to the wire-dtype round-trip of
    the feature rows; exactly identical when ``shuffle_wire_dtype``
    matches the data dtype) — enforced by
    ``tests/test_sharded_round.py``.

    The body takes an optional trailing ``params`` (a replicated traced
    :class:`~repro.core.svm.SolverParams`); the sweep subsystem vmaps
    the body over a leading config axis of (sv, params) — see
    :func:`repro.core.sweep.build_sharded_sweep_round`.
    """
    axes = tuple(axis_names)
    cap = cfg.sv_capacity
    if cap % num_devices != 0:
        raise ValueError("sv_capacity must divide the data-parallel size")
    k = cap // num_devices
    per = rows_per_device
    hosts = resolve_topology(cfg, num_devices)

    def round_body(Xl, yl, ml, sv: SVBuffer,
                   params: Optional[SolverParams] = None):
        idx = compat.axis_index(axes)           # flattened device index
        cand, w, b = _round_candidates(Xl, yl, ml, sv, cfg, axes, idx,
                                       k, per, params)
        if cfg.shuffle_impl in PACKED_SHUFFLES:
            new_sv, W, B, scores, wire_ok = _packed_merge(
                cand, w, b, Xl, cfg, axes, num_devices, k, hosts)
        else:
            new_sv = compat.tree_map(
                lambda a: compat.all_gather(a, axes, tiled=True), cand)
            # driver: eq. 7 over all-gathered hypotheses
            W = compat.all_gather(w, axes)                  # (ndev, d)
            B = compat.all_gather(b, axes)                  # (ndev,)
            scores = Xl @ W.T + B[None, :]                  # (per, ndev)
            wire_ok = None
        risks = _device_risks(scores, yl, ml, cfg, axes, num_devices)
        if wire_ok is not None:
            # wire-checksum sentinel: the host driver's eq. 8 readback
            # sees +inf and raises FaultDetected("transport", ...)
            risks = jnp.where(wire_ok, risks,
                              jnp.full_like(risks, jnp.inf))
        l_star = jnp.argmin(risks)
        return new_sv, risks, W[l_star], B[l_star]

    return round_body


def build_sharded_round(mesh, data_axes: Sequence[str], cfg: MRSVMConfig,
                        rows_per_device: int):
    """jit(shard_map(...)) one MapReduce round on ``mesh``.

    ``data_axes`` are the mesh axes the dataset rows are sharded over
    (e.g. ``("data",)`` or ``("pod", "data")``). Returns
    ``f(X, y, mask, sv) -> (sv', risks, w_best, b_best)`` where X is the
    GLOBAL array sharded on its leading axis.

    ``check_vma=False``: every output is replicated by construction
    (all_gather / psum results), which JAX's static vma checker cannot
    always infer through while_loop-heavy reducers.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(data_axes)
    ndev = int(np.prod([mesh.shape[a] for a in axes]))
    body = make_sharded_round(cfg, axes, ndev, rows_per_device)
    row_spec = P(axes if len(axes) > 1 else axes[0])
    fn = compat.shard_map(
        body, mesh=mesh,
        in_specs=(row_spec, row_spec, row_spec,
                  SVBuffer(x=P(), y=P(), alpha=P(), ids=P(), mask=P())),
        out_specs=(SVBuffer(x=P(), y=P(), alpha=P(), ids=P(), mask=P()),
                   P(), P(), P()),
        check_vma=False)
    return jax.jit(fn)
