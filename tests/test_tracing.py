"""The device programs' named scopes (DESIGN.md §17) survive ``vmap``
and ``while_loop`` into the compiled ops' ``op_name`` metadata, which
is where a device trace finds them: a refactor that drops one would
silently zero the metric that reads it."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import MRSVMConfig, SVMConfig
from repro.core.mapreduce_svm import (_final_fit_jit, _round_jit,
                                      init_sv_buffer)
from repro.core.sweep import _sweep_round_jit, stack_params

# `%name = <shape> <kind>(<operands>), ..., metadata={op_name="<path>" ...}`
OP = re.compile(r'= .*?[\]})] ([\w-]+)\(.*metadata=\{op_name="([^"]*)"')
CFG = MRSVMConfig(sv_capacity=16, gamma=1e-4, max_rounds=2,
                  svm=SVMConfig(C=1.0, max_epochs=3))
L, PER, D = 4, 8, 6


def _round():
    sv = init_sv_buffer(CFG.sv_capacity, D, jnp.float32)
    return _round_jit.lower(jnp.ones((L, PER, D)), jnp.ones((L, PER)),
                            jnp.ones((L, PER)), sv, None, cfg=CFG)


def _sweep_round():
    S = 2
    sv = init_sv_buffer(CFG.sv_capacity, D, jnp.float32)
    svb = jax.tree_util.tree_map(lambda a: jnp.stack([a] * S), sv)
    params = stack_params([CFG.svm.params()] * S)
    return _sweep_round_jit.lower(
        jnp.ones((S, L * PER, D)), jnp.ones((S, L, PER)),
        jnp.ones((S, L, PER)), svb, params, cfg=CFG, x_ax=0, m_ax=0, L=L)


def _final():
    sv = init_sv_buffer(CFG.sv_capacity, D, jnp.float32)
    return _final_fit_jit.lower(sv, None, cfg=CFG)


@pytest.mark.parametrize("lower,scopes", [
    (_round, ("svm.solve", "mr.merge", "mr.score")),
    (_sweep_round, ("svm.solve", "mr.merge", "mr.score")),
    (_final, ("svm.solve",)),
], ids=["round", "sweep_round", "final"])
def test_scopes_reach_the_compiled_ops(lower, scopes):
    ops = [m.groups() for m in map(OP.search,
                                   lower().compile().as_text().splitlines())
           if m]
    assert ops
    for scope in scopes:
        assert any(scope in name for _, name in ops), scope
    # the solver's epoch loop is one op: the scope must be on it, or a
    # union of the scoped intervals would miss the loop's time
    assert any(kind == "while" and "svm.solve" in name
               for kind, name in ops)
    assert not any("mr." in name for kind, name in ops
                   if "svm.solve" in name)
