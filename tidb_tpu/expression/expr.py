"""Expression tree + the backend-agnostic evaluator.

Reference parity: pkg/expression/expression.go (Expression, Column, Constant,
ScalarFunction) and the VecEval* machinery; serialization mirrors
expr_to_pb.go but to plain JSON-able dicts instead of tipb.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import numpy as np

from tidb_tpu.types import Datum, FieldType, TypeKind
from tidb_tpu.types.field_type import (
    bigint_type,
    bool_type,
    decimal_type,
    double_type,
    merge_types,
    string_type,
)
from tidb_tpu.types.datum import date_to_days, datetime_to_micros
from tidb_tpu.utils.chunk import Column as ChunkColumn, Dictionary
from tidb_tpu.expression.registry import REGISTRY, FuncSpec
import tidb_tpu.expression.eval  # noqa: F401  (populates REGISTRY)


class Expression:
    ftype: FieldType

    def children(self) -> Sequence["Expression"]:
        return ()

    def to_pb(self) -> dict:
        raise NotImplementedError

    # pretty-printing for EXPLAIN
    def __str__(self) -> str:
        return repr(self)


@dataclass
class ColumnRef(Expression):
    """Offset into the input schema of the operator evaluating this expr."""

    index: int
    ftype: FieldType
    name: str = ""

    def to_pb(self) -> dict:
        return {"tp": "col", "idx": self.index, "ft": _ft_pb(self.ftype)}

    def __repr__(self):
        return self.name or f"col#{self.index}"


@dataclass
class Constant(Expression):
    value: Any  # logical python value; None == NULL
    ftype: FieldType
    # EXECUTE-parameter provenance (-1 = plain constant): a cached
    # value-agnostic prepared plan rewrites ``value`` in place per execution
    # for every Constant carrying a parameter index (planner/prepcache.py)
    param_idx: int = -1

    def to_pb(self) -> dict:
        v = self.value
        if isinstance(v, bytes):
            v = v.decode("utf-8", "surrogateescape")
        elif hasattr(v, "isoformat"):
            v = v.isoformat()
        from decimal import Decimal

        if isinstance(v, Decimal):
            v = str(v)
        return {"tp": "const", "val": v, "ft": _ft_pb(self.ftype)}

    def __repr__(self):
        return "NULL" if self.value is None else repr(self.value)


@dataclass
class Operand(Expression):
    """A literal lifted out of a compiled program: its value enters the
    program as run-time operand ``slot`` (``EvalBatch.operands``), already in
    the physical representation of ``ftype``, so statements that differ only
    in such literals share one program (parallel/gather.py lifts them)."""

    slot: int
    ftype: FieldType

    def to_pb(self) -> dict:
        return {"tp": "operand", "slot": self.slot, "ft": _ft_pb(self.ftype)}

    def __repr__(self):
        return f"?{self.slot}"


@dataclass
class ScalarFunc(Expression):
    sig: str
    args: list[Expression]
    ftype: FieldType

    def children(self):
        return self.args

    def to_pb(self) -> dict:
        return {"tp": "func", "sig": self.sig, "children": [a.to_pb() for a in self.args], "ft": _ft_pb(self.ftype)}

    def __repr__(self):
        return f"{self.sig}({', '.join(map(repr, self.args))})"


# -- constructors -----------------------------------------------------------


def col(index: int, ftype: FieldType, name: str = "") -> ColumnRef:
    return ColumnRef(index, ftype, name)


def const(value: Any, ftype: Optional[FieldType] = None) -> Constant:
    if ftype is None:
        if value is None:
            ftype = FieldType(TypeKind.NULLTYPE)
        elif isinstance(value, bool):
            ftype = bool_type()
        elif isinstance(value, int):
            ftype = bigint_type().not_null()
        elif isinstance(value, float):
            ftype = double_type().not_null()
        elif isinstance(value, (str, bytes)):
            ftype = string_type().not_null()
        else:
            from decimal import Decimal

            if isinstance(value, Decimal):
                s = -value.as_tuple().exponent if value.as_tuple().exponent < 0 else 0
                ftype = decimal_type(max(len(value.as_tuple().digits), s + 1), s).not_null()
            else:
                raise TypeError(f"cannot infer type for constant {value!r}")
    return Constant(value, ftype)


def func(sig: str, *args: Expression, ret: Optional[FieldType] = None) -> ScalarFunc:
    spec = REGISTRY.get(sig)
    if spec is None:
        raise KeyError(f"unknown builtin {sig!r}")
    arglist = list(args)
    if ret is None:
        ret = spec.infer([a.ftype for a in arglist])
    return ScalarFunc(sig, arglist, ret)


# -- serialization ----------------------------------------------------------


def _ft_pb(ft: FieldType) -> list:
    return [int(ft.kind), ft.length, ft.scale, int(ft.nullable), ft.collation, int(ft.json)]


def _ft_from_pb(v: list) -> FieldType:
    return FieldType(
        TypeKind(v[0]),
        length=v[1],
        scale=v[2],
        nullable=bool(v[3]),
        collation=v[4],
        json=bool(v[5]) if len(v) > 5 else False,
    )


def expr_from_pb(pb: dict) -> Expression:
    tp = pb["tp"]
    if tp == "col":
        return ColumnRef(pb["idx"], _ft_from_pb(pb["ft"]))
    if tp == "const":
        ft = _ft_from_pb(pb["ft"])
        v = pb["val"]
        if isinstance(v, str) and ft.kind == TypeKind.STRING:
            v = v.encode("utf-8", "surrogateescape")
        return Constant(v, ft)
    if tp == "func":
        return ScalarFunc(pb["sig"], [expr_from_pb(c) for c in pb["children"]], _ft_from_pb(pb["ft"]))
    raise ValueError(f"bad expr pb {pb!r}")


# -- pushdown legality (ref: infer_pushdown.go:85) --------------------------

_TPU_STRING_OK = {"eq", "ne", "in", "isnull", "ifnull", "coalesce", "if", "case_when"}
_TPU_STRING_ORDER = {"lt", "le", "gt", "ge"}  # legal only with sorted dicts (bind-time check)


def can_push_down(expr: Expression, engine: str) -> bool:
    if isinstance(expr, ScalarFunc):
        spec = REGISTRY.get(expr.sig)
        if spec is None or engine not in spec.engines:
            return False
        if engine == "tpu":
            has_str = any(a.ftype.kind == TypeKind.STRING for a in expr.args)
            if has_str and expr.sig not in (_TPU_STRING_OK | _TPU_STRING_ORDER):
                return False
            # ci collation folds at compare time — dictionary codes on the
            # device are raw-bytes identities, so these stay host-side
            # (ref: pushdown disabled for new collations, infer_pushdown.go)
            if any(
                a.ftype.kind == TypeKind.STRING and a.ftype.collation == "ci" for a in expr.args
            ):
                return False
        return all(can_push_down(a, engine) for a in expr.args)
    return True


# ---------------------------------------------------------------------------
# evaluation
# ---------------------------------------------------------------------------


@dataclass
class EvalBatch:
    """Input columns for one operator: parallel (data, validity) pairs.
    validity None = all valid. ``dicts[i]`` set for string columns.
    ``warn(level, code, msg)``: per-statement warning sink (ref: stmtctx
    AppendWarning, pkg/sessionctx/stmtctx/stmtctx.go:1025) — host-side eval
    reports truncation/zero-division through it; device traces leave it
    None (a jitted program cannot append per-row diagnostics)."""

    cols: list[tuple]
    dicts: list[Optional[Dictionary]]
    n: int
    warn: Optional[object] = None
    operands: Optional[Sequence] = None  # what each `Operand` slot holds

    @staticmethod
    def from_chunk(chunk, warn=None) -> "EvalBatch":
        cols = [(c.data, c.validity) for c in chunk.columns]
        dicts = [c.dictionary for c in chunk.columns]
        return EvalBatch(cols, dicts, len(chunk), warn)


class _Ctx:
    __slots__ = ("args", "arg_types", "arg_dicts", "ret_type", "ret_dict", "n", "warn")

    def __init__(self, args, arg_types, arg_dicts, ret_type, ret_dict, n, warn=None):
        self.args = args
        self.arg_types = arg_types
        self.arg_dicts = arg_dicts
        self.ret_type = ret_type
        self.ret_dict = ret_dict
        self.n = n
        self.warn = warn


def _const_physical(c: Constant, xp):
    """Lower a constant to its device scalar. Strings yield raw bytes — the
    caller (binder or host evaluator) maps them onto a dictionary."""
    if c.value is None:
        return 0, False
    k = c.ftype.kind
    if k == TypeKind.STRING:
        v = c.value
        if isinstance(v, str):
            v = v.encode("utf-8")
        return v, None
    return Datum(c.value, c.ftype).physical(), None


def eval_expr(expr: Expression, batch: EvalBatch, xp=np):
    """→ (data, validity, dictionary|None). Fully traceable under jax.jit
    when every builtin in the tree is tpu-legal and strings are pre-bound."""
    if isinstance(expr, ColumnRef):
        d, v = batch.cols[expr.index]
        return d, v, batch.dicts[expr.index]
    if isinstance(expr, Operand):
        return batch.operands[expr.slot], None, None
    if isinstance(expr, Constant):
        pv, valid = _const_physical(expr, xp)
        if isinstance(pv, bytes):
            dic = Dictionary()
            return dic.encode(pv), valid, dic
        return pv, valid, None
    if isinstance(expr, ScalarFunc):
        spec = REGISTRY[expr.sig]
        args = []
        dicts = []
        for a in expr.args:
            d, v, dic = eval_expr(a, batch, xp)
            args.append((d, v))
            dicts.append(dic)
        ret_dict = Dictionary() if expr.ftype.kind == TypeKind.STRING else None
        ctx = _Ctx(args, [a.ftype for a in expr.args], dicts, expr.ftype, ret_dict, batch.n, batch.warn)
        d, v = spec.impl(xp, args, ctx)
        return d, v, ret_dict
    raise TypeError(f"cannot evaluate {expr!r}")


def eval_to_column(expr: Expression, batch: EvalBatch, xp=np) -> ChunkColumn:
    """Host-side convenience: evaluate and materialize a chunk Column."""
    d, v, dic = eval_expr(expr, batch, xp)
    n = batch.n
    d = np.asarray(d)
    if d.ndim == 0:
        d = np.broadcast_to(d, (n,)).copy()
    if v is None:
        v = np.ones(n, dtype=bool)
    elif v is False or (np.isscalar(v) and not v):
        v = np.zeros(n, dtype=bool)
    else:
        v = np.asarray(v)
        if v.ndim == 0:
            v = np.broadcast_to(v, (n,)).copy()
    dtype = {TypeKind.FLOAT: np.float64, TypeKind.STRING: np.int32}.get(expr.ftype.kind, np.int64)
    return ChunkColumn(d.astype(dtype), v.astype(bool), expr.ftype, dic)


# ---------------------------------------------------------------------------
# aggregates (descriptors; execution lives in the engines)
# ---------------------------------------------------------------------------

AGG_FUNCS = {
    "count",
    "sum",
    "avg",
    "min",
    "max",
    "first_row",
    "group_concat",
    "stddev_pop",
    "stddev_samp",
    "var_pop",
    "var_samp",
    "bit_and",
    "bit_or",
    "bit_xor",
}
# variance family shares the (count, sum, sumsq) partial state
VAR_AGGS = {"stddev_pop", "stddev_samp", "var_pop", "var_samp"}
BIT_AGGS = {"bit_and", "bit_or", "bit_xor"}


@dataclass
class AggDesc:
    """ref: pkg/expression/aggregation.AggFuncDesc. ``partial_kinds`` names
    the device-state lanes the partial stage produces; the final stage merges
    them (two-phase agg: copr partial on shards → final at root / exchange)."""

    name: str
    arg: Optional[Expression]  # None for COUNT(*)
    distinct: bool = False
    sep: str = ","  # GROUP_CONCAT separator
    # GROUP_CONCAT(... ORDER BY e [DESC], ...): [(Expression, desc)]
    order_by: list = field(default_factory=list)

    @property
    def ftype(self) -> FieldType:
        if self.name == "count":
            return bigint_type(nullable=False)
        if self.name == "group_concat":
            from tidb_tpu.types.field_type import string_type

            return string_type()
        at = self.arg.ftype
        if self.name == "sum":
            if at.kind == TypeKind.DECIMAL:
                return decimal_type(38, at.scale)
            if at.kind == TypeKind.FLOAT:
                return double_type()
            return bigint_type()
        if self.name == "avg":
            if at.kind == TypeKind.DECIMAL:
                return decimal_type(38, min(at.scale + 4, 30))
            return double_type()
        if self.name in VAR_AGGS:
            return double_type()
        if self.name in BIT_AGGS:
            # MySQL bit aggregates are BIGINT UNSIGNED: the BIT_AND identity
            # (all ones) must render as 18446744073709551615, not -1
            return FieldType(TypeKind.UINT, nullable=False)
        return at  # min/max/first_row

    @property
    def partial_kinds(self) -> list[str]:
        if self.name == "count":
            return ["count"]
        if self.name == "sum":
            return ["sum"]
        if self.name == "avg":
            return ["count", "sum"]
        if self.name in ("min", "max", "first_row"):
            return [self.name]
        if self.name in VAR_AGGS:
            return ["count", "sum", "sumsq"]
        if self.name in BIT_AGGS:
            return [self.name]
        if self.name == "group_concat":
            # no distributable partial state: the planner keeps group_concat
            # at the complete (root) stage
            return ["group_concat"]
        raise ValueError(self.name)

    def to_pb(self) -> dict:
        return {
            "name": self.name,
            "arg": self.arg.to_pb() if self.arg is not None else None,
            "distinct": self.distinct,
            "sep": self.sep,
            "order_by": [(e.to_pb(), d) for e, d in self.order_by],
        }

    @staticmethod
    def from_pb(pb: dict) -> "AggDesc":
        return AggDesc(
            pb["name"],
            expr_from_pb(pb["arg"]) if pb["arg"] is not None else None,
            pb["distinct"],
            pb.get("sep", ","),
            order_by=[(expr_from_pb(e), d) for e, d in pb.get("order_by", [])],
        )

    def __repr__(self):
        inner = "*" if self.arg is None else repr(self.arg)
        sep = f" separator={self.sep!r}" if self.name == "group_concat" and self.sep != "," else ""
        ob = ""
        if self.order_by:
            keys = ", ".join(f"{e!r}{' desc' if d else ''}" for e, d in self.order_by)
            ob = f" order by {keys}"
        return f"{self.name}({'distinct ' if self.distinct else ''}{inner}{ob}{sep})"
