"""``recompile-hazard``: jit keys must route through pow2 bucketing.

Every distinct input shape (and every distinct static value) is a new
XLA compile.  The serving stack keeps compile counts bounded by padding
data-dependent sizes through the pow2/bucketing helpers
(``_pow2_at_least`` / ``_pad_pow2`` / ``_pad_rows`` / ``_pad_feat`` /
``_flat_bucket``) and the persisted ``*_cap`` attributes before
anything reaches a jitted callable.  This rule flags two ways a change
can silently reintroduce per-request compiles:

* a jitted callee fed ``jnp.asarray(x)`` / ``jnp.array(x)`` where ``x``
  involves a locally-assigned array that never went through a bucketing
  helper (raw data-dependent shape -> one compile per batch size);
* a ``static_argnames`` keyword receiving an array-constructor value
  (arrays are unhashable -- a guaranteed ``TypeError`` at trace time,
  or worse, a compile per value if converted).
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from ..context import (FunctionUnit, JitSpec, ModuleInfo,
                       ProjectContext, dotted_name, iter_assignments)
from ..registry import Rule, register_rule
from ..report import Violation

#: helpers whose output is shape-bucketed by construction
BUCKETING_HELPERS = frozenset({
    "_pow2_at_least", "_pad_pow2", "_pad_rows", "_pad_feat",
    "_flat_bucket",
})

_CONVERTERS = frozenset({
    "jnp.asarray", "jnp.array", "jax.numpy.asarray", "jax.numpy.array",
})

_ARRAY_CTORS = frozenset({
    "np.array", "np.asarray", "np.zeros", "np.ones", "np.empty",
    "jnp.array", "jnp.asarray", "jnp.zeros", "jnp.ones",
    "numpy.array", "numpy.asarray", "numpy.zeros", "numpy.ones",
})


def _bucketed_names(unit: FunctionUnit) -> Set[str]:
    """Names assigned (in source order) from a bucketing helper, a
    ``*_cap`` attribute, or another bucketed name."""
    bucketed: Set[str] = set()

    def value_is_bucketed(value: ast.AST) -> bool:
        for sub in ast.walk(value):
            if isinstance(sub, ast.Call):
                callee = sub.func
                simple = (callee.id if isinstance(callee, ast.Name)
                          else callee.attr
                          if isinstance(callee, ast.Attribute) else "")
                if simple in BUCKETING_HELPERS:
                    return True
            if isinstance(sub, ast.Attribute) and \
                    sub.attr.endswith("_cap"):
                return True
            if isinstance(sub, ast.Name) and sub.id in bucketed:
                return True
        return False

    for names, value, _line in sorted(
            iter_assignments(unit.node), key=lambda t: t[2]):
        if value_is_bucketed(value):
            bucketed.update(n for n in names if "." not in n)
    return bucketed


def _assigned_names(unit: FunctionUnit) -> Set[str]:
    out: Set[str] = set()
    for names, _value, _line in iter_assignments(unit.node):
        out.update(n for n in names if "." not in n)
    return out


@register_rule
class RecompileHazard(Rule):
    name = "recompile-hazard"
    description = ("jitted callable fed raw data-dependent shapes that "
                   "skip pow2 bucketing, or an array-typed "
                   "static_argnames value")

    def check_module(self, mod: ModuleInfo,
                     ctx: ProjectContext) -> List[Violation]:
        out: List[Violation] = []
        for unit in mod.units:
            out.extend(self._check_unit(mod, ctx, unit))
        return out

    def _check_unit(self, mod: ModuleInfo, ctx: ProjectContext,
                    unit: FunctionUnit) -> List[Violation]:
        out: List[Violation] = []
        bucketed = _bucketed_names(unit)
        assigned = _assigned_names(unit)
        for node in ast.walk(unit.node):
            if not isinstance(node, ast.Call):
                continue
            spec = ctx.resolve_jitted_callee(mod, node)
            if spec is None:
                continue
            callee = dotted_name(node.func) or "<jitted>"
            out.extend(self._check_raw_shapes(
                mod, node, callee, bucketed, assigned))
            out.extend(self._check_static_args(mod, node, callee, spec))
        return out

    def _check_raw_shapes(self, mod: ModuleInfo, call: ast.Call,
                          callee: str, bucketed: Set[str],
                          assigned: Set[str]) -> List[Violation]:
        out: List[Violation] = []
        args = list(call.args) + [kw.value for kw in call.keywords]
        for arg in args:
            for sub in ast.walk(arg):
                if not isinstance(sub, ast.Call):
                    continue
                if dotted_name(sub.func) not in _CONVERTERS:
                    continue
                raw = self._raw_name(sub, bucketed, assigned)
                if raw is not None:
                    out.append(Violation(
                        rule=self.name, path=mod.path,
                        line=sub.lineno, col=sub.col_offset,
                        message=(f"{callee}() is fed a device array "
                                 f"built from '{raw}', whose shape "
                                 "never went through a bucketing "
                                 "helper (_pad_pow2/_pow2_at_least); "
                                 "each distinct size is a fresh XLA "
                                 "compile")))
        return out

    @staticmethod
    def _raw_name(conv: ast.Call, bucketed: Set[str],
                  assigned: Set[str]) -> Optional[str]:
        for sub in ast.walk(conv):
            if isinstance(sub, ast.Name) and sub.id in assigned and \
                    sub.id not in bucketed:
                return sub.id
        return None

    def _check_static_args(self, mod: ModuleInfo, call: ast.Call,
                           callee: str,
                           spec: JitSpec) -> List[Violation]:
        out: List[Violation] = []
        statics = set(spec.static_argnames)
        if not statics:
            return out
        for kw in call.keywords:
            if kw.arg not in statics:
                continue
            if isinstance(kw.value, ast.Call) and \
                    dotted_name(kw.value.func) in _ARRAY_CTORS:
                out.append(Violation(
                    rule=self.name, path=mod.path,
                    line=kw.value.lineno, col=kw.value.col_offset,
                    message=(f"static argument '{kw.arg}' of "
                             f"{callee}() receives an array value; "
                             "static_argnames must be hashable and "
                             "low-cardinality (this is a trace-time "
                             "TypeError or a compile per value)")))
        return out
