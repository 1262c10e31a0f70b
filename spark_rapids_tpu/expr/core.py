"""Expression tree core: the engine's analog of Catalyst expressions plus
their columnar TPU evaluation (the reference's Gpu* expression hierarchy,
e.g. arithmetic.scala / predicates / conditionalExpressions across
sql-plugin; ~218 expr rules in GpuOverrides.scala:919).

Every expression evaluates columnar: `columnar_eval(batch) -> Column`, a pure
traced-jax function of the batch, so whole projections jit into one XLA
program and fuse (the TPU-side advantage over per-kernel cuDF dispatch).

Null semantics follow Spark exactly: null-intolerant operators AND child
validities; special forms (And/Or/If/Coalesce) implement Spark's 3-valued
logic explicitly on validity lanes.
"""

from __future__ import annotations

import datetime
from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, StringColumn
from ..types import (
    BOOLEAN, BooleanType, DataType, DoubleType, NullType, StringType,
)


class Expression:
    """Base expression node. Immutable; children in `children`."""

    children: Sequence["Expression"] = ()

    @property
    def data_type(self) -> DataType:
        raise NotImplementedError(type(self).__name__)

    @property
    def nullable(self) -> bool:
        return True

    def columnar_eval(self, batch: ColumnarBatch) -> Column:
        raise NotImplementedError(type(self).__name__)

    # -- traversal helpers -------------------------------------------------
    def transform_up(self, fn):
        new_children = [c.transform_up(fn) for c in self.children]
        node = self.with_children(new_children) if new_children else self
        return fn(node)

    def with_children(self, children: List["Expression"]) -> "Expression":
        if not self.children:
            return self
        raise NotImplementedError(type(self).__name__)

    def collect(self, pred) -> List["Expression"]:
        out = [self] if pred(self) else []
        for c in self.children:
            out.extend(c.collect(pred))
        return out

    @property
    def deterministic(self) -> bool:
        return all(c.deterministic for c in self.children)

    def __repr__(self):
        args = ", ".join(repr(c) for c in self.children)
        return f"{type(self).__name__}({args})"

    # convenience operator sugar (DataFrame API uses these)
    def _bin(self, other, cls):
        from . import arithmetic, predicates  # noqa
        return cls(self, lit(other) if not isinstance(other, Expression) else other)

    def __add__(self, other):
        from .arithmetic import Add
        return self._bin(other, Add)

    def __sub__(self, other):
        from .arithmetic import Subtract
        return self._bin(other, Subtract)

    def __mul__(self, other):
        from .arithmetic import Multiply
        return self._bin(other, Multiply)

    def __truediv__(self, other):
        from .arithmetic import Divide
        return self._bin(other, Divide)

    def __mod__(self, other):
        from .arithmetic import Remainder
        return self._bin(other, Remainder)

    def __neg__(self):
        from .arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __eq__(self, other):  # type: ignore[override]
        from .predicates import EqualTo
        return self._bin(other, EqualTo)

    def __ne__(self, other):  # type: ignore[override]
        from .predicates import Not, EqualTo
        return Not(self._bin(other, EqualTo))

    def __lt__(self, other):
        from .predicates import LessThan
        return self._bin(other, LessThan)

    def __le__(self, other):
        from .predicates import LessThanOrEqual
        return self._bin(other, LessThanOrEqual)

    def __gt__(self, other):
        from .predicates import GreaterThan
        return self._bin(other, GreaterThan)

    def __ge__(self, other):
        from .predicates import GreaterThanOrEqual
        return self._bin(other, GreaterThanOrEqual)

    def __and__(self, other):
        from .predicates import And
        return self._bin(other, And)

    def __or__(self, other):
        from .predicates import Or
        return self._bin(other, Or)

    def __invert__(self):
        from .predicates import Not
        return Not(self)

    def __hash__(self):
        return id(self)

    def semantic_key(self):
        """Structural identity for CSE (the tiered-project dedupe,
        reference GpuTieredProject basicPhysicalOperators.scala:507)."""
        return (type(self).__name__, self._semantic_args(),
                tuple(c.semantic_key() for c in self.children))

    def _semantic_args(self):
        return ()

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def cast(self, dt: DataType) -> "Expression":
        from .cast import Cast
        return Cast(self, dt)


class LeafExpression(Expression):
    children = ()

    def with_children(self, children):
        assert not children
        return self


class Literal(LeafExpression):
    def __init__(self, value, dtype: Optional[DataType] = None):
        self._dtype = dtype or _infer_literal_type(value)
        if isinstance(value, datetime.date) \
                and not isinstance(value, datetime.datetime):
            # a DATE is days since the epoch (types.DateType): kept as
            # the column holds it, so the literal can be evaluated
            value = value.toordinal() - _EPOCH_ORDINAL
        self.value = value

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self.value is None

    def columnar_eval(self, batch: ColumnarBatch) -> Column:
        cap = batch.capacity
        dt = self._dtype
        if isinstance(dt, StringType):
            b = (self.value or "").encode("utf-8") if isinstance(self.value, str) \
                else (self.value or b"")
            n_bytes = max(len(b), 1)
            from ..columnar.column import bucket_capacity
            byte_cap = bucket_capacity(n_bytes * cap)
            lengths = jnp.full((cap,), len(b), jnp.int32)
            offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                       jnp.cumsum(lengths, dtype=jnp.int32)])
            pattern = np.frombuffer(b, dtype=np.uint8) if b else np.zeros(0, np.uint8)
            reps = int(np.ceil(byte_cap / max(len(b), 1)))
            data = np.tile(pattern, reps)[:byte_cap] if len(b) else np.zeros(byte_cap, np.uint8)
            valid = jnp.full((cap,), self.value is not None)
            return StringColumn(jnp.asarray(data), offsets, valid, dt)
        if self.value is None:
            zero = jnp.zeros((cap,), dt.jnp_dtype if dt.jnp_dtype else jnp.int8)
            return Column(zero, jnp.zeros((cap,), jnp.bool_), dt)
        data = jnp.full((cap,), self.value, dt.jnp_dtype)
        return Column(data, jnp.ones((cap,), jnp.bool_), dt)

    def _semantic_args(self):
        return (self.value, repr(self._dtype))

    def __repr__(self):
        return f"lit({self.value!r})"


_EPOCH_ORDINAL = datetime.date(1970, 1, 1).toordinal()


def _infer_literal_type(value) -> DataType:
    from ..types import (BOOLEAN, DOUBLE, INT, LONG, NULL, STRING)
    if value is None:
        return NULL
    if isinstance(value, bool):
        return BOOLEAN
    if isinstance(value, int):
        return INT if -(2**31) <= value < 2**31 else LONG
    if isinstance(value, float):
        return DOUBLE
    if isinstance(value, (str, bytes)):
        return STRING
    if isinstance(value, datetime.date) and not isinstance(value, datetime.datetime):
        from ..types import DATE
        return DATE
    raise TypeError(f"cannot infer literal type for {value!r}")


def lit(value) -> Expression:
    return value if isinstance(value, Expression) else Literal(value)


class BoundReference(LeafExpression):
    """Resolved column reference by ordinal (Catalyst BoundReference)."""

    def __init__(self, ordinal: int, dtype: DataType, name: str = ""):
        self.ordinal = ordinal
        self._dtype = dtype
        self.name = name

    @property
    def data_type(self):
        return self._dtype

    def columnar_eval(self, batch: ColumnarBatch) -> Column:
        return batch.columns[self.ordinal]

    def _semantic_args(self):
        return (self.ordinal,)

    def __repr__(self):
        return f"#{self.ordinal}:{self.name}"


class UnresolvedAttribute(LeafExpression):
    """Named column reference; resolved against a schema during planning."""

    def __init__(self, name: str):
        self.name = name

    @property
    def data_type(self):
        raise TypeError(f"unresolved attribute {self.name!r}")

    def columnar_eval(self, batch: ColumnarBatch) -> Column:
        return batch.column(self.name)

    def _semantic_args(self):
        return (self.name,)

    def __repr__(self):
        return f"col({self.name!r})"


def col(name: str) -> UnresolvedAttribute:
    return UnresolvedAttribute(name)


class Alias(Expression):
    def __init__(self, child: Expression, name: str):
        self.children = (child,)
        self.name = name

    @property
    def child(self):
        return self.children[0]

    @property
    def data_type(self):
        return self.child.data_type

    @property
    def nullable(self):
        return self.child.nullable

    def columnar_eval(self, batch):
        return self.child.columnar_eval(batch)

    def with_children(self, children):
        return Alias(children[0], self.name)

    def _semantic_args(self):
        return ()  # alias is transparent for CSE

    def semantic_key(self):
        return self.children[0].semantic_key()

    def __repr__(self):
        return f"{self.children[0]!r} AS {self.name}"


def resolve(expr: Expression, schema) -> Expression:
    """Bind UnresolvedAttribute -> BoundReference against `schema`."""
    def fn(node):
        if isinstance(node, UnresolvedAttribute):
            idx = schema.index_of(node.name)
            return BoundReference(idx, schema.fields[idx].data_type, node.name)
        return node
    return expr.transform_up(fn)


def output_name(expr: Expression, default: str) -> str:
    if isinstance(expr, Alias):
        return expr.name
    if isinstance(expr, (UnresolvedAttribute, BoundReference)):
        return expr.name
    return default
