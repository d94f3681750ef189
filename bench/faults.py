"""Faults of the timed path, for showing that the comparison catches
them: each is a list of ``(module or class, attribute, replacement)``
that breaks the program underneath the harness once set. The fold
cell's: a fold that publishes its input state unchanged, one that
leaves half of its new rows out, and an answer altered where it is
produced. The training cell's: rounds that return their SV state
unchanged, a fit on half of the rows, and an altered answer. (The cells
run on one chip, so there is no exchange between chips to leave out.)
"""
from __future__ import annotations


def stale_fold():
    from repro.serving.svm_stream import StreamingSVMService

    def stale(self, joined, names, swapped):
        for s in names:
            snap = joined[s][0]
            self._swap(s, snap.model, snap.params)
            swapped.append(s)
    return [(StreamingSVMService, "_fold_batched", stale)]


def half_fold():
    import repro.serving.svm_stream as stream
    orig = stream.fit_mapreduce_sweep

    def half(X, y, L, cfg, params, mask=None, **kw):
        new = X.shape[-2] - cfg.sv_capacity
        return orig(X, y, L, cfg, params,
                    mask=mask.at[:, :new // 2].set(0), **kw)
    return [(stream, "fit_mapreduce_sweep", half)]


def altered_fold():
    from repro.serving.svm_stream import StreamingSVMService
    orig = StreamingSVMService._swap

    def swap(self, stream, model, params):
        final = model.final._replace(w=model.final.w * 1.01)
        return orig(self, stream, model._replace(final=final), params)
    return [(StreamingSVMService, "_swap", swap)]


def stale_fit():
    import repro.core.mapreduce_svm as mr
    orig = mr._round_jit

    def same(Xp, yp, maskp, sv, params, cfg):
        return orig(Xp, yp, maskp, sv, params, cfg=cfg)._replace(sv=sv)
    return [(mr, "_round_jit", same)]


def half_fit():
    import jax.numpy as jnp
    import repro.core as core
    orig = core.fit_mapreduce

    def half(X, y, L, cfg, mask=None, **kw):
        n = X.shape[0]
        m = jnp.ones((n,), X.dtype).at[: n // 2].set(0)
        return orig(X, y, L, cfg, mask=m, **kw)
    return [(core, "fit_mapreduce", half)]


def altered_fit():
    import repro.core as core
    orig = core.fit_mapreduce

    def alter(*a, **kw):
        m = orig(*a, **kw)
        return m._replace(final=m.final._replace(w=m.final.w * 1.01))
    return [(core, "fit_mapreduce", alter)]


BY_MODE = {"closed": (stale_fold, half_fold, altered_fold),
           "train": (stale_fit, half_fit, altered_fit)}
