"""Static analyzer for the ``kernels/dp_fill`` Pallas kernels.

PR 5's fused fill ships with a *hand* proof that its revisited whole-array
output blocks are safe: every garbage row a padded static-height slice
writes "always belongs to later bands and is rewritten by its own band's
step before any read" (see ``_FusedOperands``).  This module machine-checks
that argument — and the per-band kernels' accumulator/grid discipline —
directly from the kernel *sources* (``ast``; the kernels are never imported,
so the analyzer runs without jax).

How: an abstract interpreter executes each kernel body over the real
sequential TPU grid order (last dimension innermost) for a matrix of small
concrete instantiations ``(L, BR, allow_fall, host_on)``.  Index arithmetic
(`pl.program_id`, ``off_ref[...]`` reads, ``pl.ds`` bounds) is evaluated
*concretely*; array values are abstracted to per-row validity lanes.  Rows
of carried (revisited output) buffers start invalid; reads AND their lanes
into everything derived from them; writes store the result lanes.  The
checks:

- **out-of-bounds** — every ``pl.ds`` slice and scalar index on every
  buffer stays inside the driver-contract shapes (``nrows = ncells + 2L +
  BR`` row pad, ``vec = 2L + BR + 2`` vectors, ``(L, rt·BR)`` threshold
  mats — mirrored from ``ops._FusedOperands``);
- **write-before-read domination / final validity** — after the full grid,
  every *real* table row (``[0, ncells)``) must carry valid lanes: a read
  of a garbage row only taints lanes that are later overwritten by their
  own band, or the proof fails;
- **clobber** — no write may turn an already-valid row invalid (a garbage
  write landing on a finalized row is exactly the race the pad-margin
  argument rules out);
- **grid discipline** (per-band kernels) — the output BlockSpec index maps,
  extracted from the drivers' ``pallas_call`` and evaluated over the grid,
  must be constant along the innermost (split) dimension — the revisited
  accumulator contract — and pairwise disjoint across row tiles
  (write-disjointness for non-revisited steps).

Known-sound / known-incomplete boundary: rows are tracked exactly;
*columns* are not (all gathers are within-row ``take_along_axis`` whose
clamp ladder is part of the trusted pattern), float semantics are trusted
(IEEE min/max), and the driver contract (shapes, band offsets, base-case
validity) is asserted against ``ops.py`` by ``tests/test_check_kernel_analyzer``
rather than derived.  Anything the interpreter cannot model is reported as
an ``unsupported`` issue — the gate fails closed.

Results are keyed by :func:`repro.core.solver_cache.code_fingerprint` (which
already hashes the kernel sources): ``python -m repro.check`` skips the
analysis when the fingerprint matches the last recorded pass.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

ISSUE_KINDS = (
    "out-of-bounds",      # slice/index escapes the driver-contract shape
    "final-invalid",      # a real table row ends the grid with garbage lanes
    "clobber",            # a write turned an already-valid row invalid
    "grid-race",          # out BlockSpec not revisited/disjoint as required
    "read-only-write",    # kernel writes an input buffer
    "unsupported",        # construct outside the modeled subset (fail closed)
)


@dataclasses.dataclass(frozen=True)
class KernelIssue:
    kernel: str
    kind: str
    message: str
    case: str = ""

    def __post_init__(self):
        if self.kind not in ISSUE_KINDS:
            raise ValueError(f"unknown issue kind {self.kind!r}")

    def __str__(self) -> str:
        where = f" [{self.case}]" if self.case else ""
        return f"{self.kernel}: {self.kind}: {self.message}{where}"


class _Unsupported(Exception):
    pass


class _IssueStop(Exception):
    """Raised to abort a case after too many issues."""


# -- abstract values ---------------------------------------------------------

VALID = object()  # fully-valid array of unknown lane structure


class Lanes:
    """Per-row validity of an array value whose leading axis is rows."""

    __slots__ = ("mask",)

    def __init__(self, mask: Sequence[bool]):
        self.mask = list(mask)


class DS:
    """A ``pl.ds(start, size)`` slice with concrete bounds."""

    __slots__ = ("start", "size")

    def __init__(self, start: int, size: int):
        self.start = int(start)
        self.size = int(size)


class FuncVal:
    """A def/lambda closure interpreted on call."""

    __slots__ = ("node", "env")

    def __init__(self, node: ast.AST, env: Dict[str, Any]):
        self.node = node
        self.env = env


def _combine(*values: Any) -> Any:
    """Validity meet: any invalid lane in any row-shaped operand taints the
    corresponding output lane (row-aligned elementwise/broadcast ops)."""
    out: Any = VALID
    for v in values:
        if isinstance(v, Lanes):
            if out is VALID:
                out = Lanes(v.mask)
            elif isinstance(out, Lanes):
                if len(out.mask) != len(v.mask):
                    raise _Unsupported(
                        f"combining lanes of different heights "
                        f"({len(out.mask)} vs {len(v.mask)})"
                    )
                out = Lanes(
                    [a and b for a, b in zip(out.mask, v.mask)]
                )
    return out


# -- buffers -----------------------------------------------------------------


class Buf:
    """One kernel ref: concrete shape, optional per-row validity, optional
    concrete integer contents (the band-offset vector)."""

    def __init__(
        self,
        name: str,
        shape: Tuple[int, ...],
        *,
        readonly: bool,
        valid: Optional[List[bool]] = None,
        values: Optional[List[int]] = None,
        window: Optional[Tuple[int, int]] = None,
    ):
        self.name = name
        self.shape = shape
        self.readonly = readonly
        self.valid = valid  # None => always-valid input
        self.values = values
        self.window = window  # (lo, hi) rows bound at this grid step


# -- the interpreter ---------------------------------------------------------


class _Interp:
    def __init__(
        self,
        module_env: Dict[str, Any],
        functions: Dict[str, ast.FunctionDef],
        issues: List[KernelIssue],
        kernel_name: str,
        case: str,
        max_issues: int = 8,
    ):
        self.module_env = module_env
        self.functions = functions
        self.issues = issues
        self.kernel = kernel_name
        self.case = case
        self.pids: Tuple[int, ...] = ()
        self.max_issues = max_issues

    def issue(self, kind: str, message: str) -> None:
        self.issues.append(
            KernelIssue(self.kernel, kind, message, self.case)
        )
        if len(self.issues) >= self.max_issues:
            raise _IssueStop()

    # -- buffer access ----------------------------------------------------

    def _slice_1d(self, buf: Buf, idx: Any, ctx: str) -> Tuple[int, int]:
        """Resolve an index on the leading axis to concrete (lo, hi)."""
        n = buf.shape[0]
        if isinstance(idx, DS):
            lo, hi = idx.start, idx.start + idx.size
        elif isinstance(idx, (int, bool)):
            lo, hi = int(idx), int(idx) + 1
        else:
            raise _Unsupported(f"non-concrete index on {buf.name} ({ctx})")
        if lo < 0 or hi > n:
            self.issue(
                "out-of-bounds",
                f"{ctx} rows [{lo}, {hi}) escape {buf.name}"
                f"[0, {n})",
            )
            lo, hi = max(lo, 0), min(hi, n)
        return lo, hi

    def read_buf(self, buf: Buf, index: Any) -> Any:
        if buf.window is not None:  # pre-sliced block (per-band kernels)
            if buf.valid is None:
                return VALID
            lo, hi = buf.window
            return Lanes(buf.valid[lo:hi])
        if index is Ellipsis:
            if buf.valid is None:
                return VALID
            return Lanes(list(buf.valid))
        idx = index[0] if isinstance(index, tuple) else index
        if isinstance(idx, (int, bool)) and buf.values is not None:
            i = int(idx)
            if not (0 <= i < buf.shape[0]):
                self.issue(
                    "out-of-bounds",
                    f"scalar read {buf.name}[{i}] escapes "
                    f"[0, {buf.shape[0]})",
                )
                return 0
            return buf.values[i]
        if isinstance(index, tuple) and len(index) == 2:
            a, b = index
            # ref[pl.ds(row, 1), pl.ds(col, BR)] over the (L, rt·BR) mats
            if isinstance(a, DS) and isinstance(b, DS):
                lo0, hi0 = self._slice_1d(buf, a, f"read {buf.name}")
                if b.start < 0 or b.start + b.size > buf.shape[1]:
                    self.issue(
                        "out-of-bounds",
                        f"read {buf.name} cols [{b.start}, "
                        f"{b.start + b.size}) escape [0, {buf.shape[1]})",
                    )
                return VALID if buf.valid is None else Lanes(
                    buf.valid[lo0:hi0]
                )
        lo, hi = self._slice_1d(buf, idx, f"read {buf.name}")
        if buf.valid is None:
            return VALID
        return Lanes(buf.valid[lo:hi])

    def write_buf(self, buf: Buf, index: Any, value: Any) -> None:
        if buf.readonly:
            self.issue(
                "read-only-write", f"write to input buffer {buf.name}"
            )
            return
        if buf.window is not None:
            lo, hi = buf.window
        elif index is Ellipsis:
            lo, hi = 0, buf.shape[0]
        else:
            idx = index[0] if isinstance(index, tuple) else index
            lo, hi = self._slice_1d(buf, idx, f"write {buf.name}")
        h = hi - lo
        if value is VALID or isinstance(value, (int, float, bool)):
            new = [True] * h
        elif isinstance(value, Lanes):
            if len(value.mask) != h:
                raise _Unsupported(
                    f"write of {len(value.mask)} lanes into {h} rows "
                    f"of {buf.name}"
                )
            new = list(value.mask)
        else:
            raise _Unsupported(
                f"write of unmodeled value into {buf.name}"
            )
        assert buf.valid is not None
        for k in range(h):
            if buf.valid[lo + k] and not new[k]:
                self.issue(
                    "clobber",
                    f"write invalidates finalized row {lo + k} of "
                    f"{buf.name}",
                )
        buf.valid[lo:hi] = new

    # -- expression evaluation --------------------------------------------

    def _dotted(self, node: ast.AST) -> Optional[str]:
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    def eval(self, node: ast.AST, env: Dict[str, Any]) -> Any:
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            if node.id in self.module_env:
                return self.module_env[node.id]
            if node.id in self.functions:
                return FuncVal(self.functions[node.id], {})
            raise _Unsupported(f"unknown name {node.id!r}")
        if isinstance(node, ast.Tuple):
            return tuple(self.eval(e, env) for e in node.elts)
        if isinstance(node, ast.Attribute):
            dotted = self._dotted(node)
            if dotted in ("jnp.inf", "np.inf"):
                return float("inf")
            return VALID  # jnp.float32, COST_DT-as-attr, dtypes, ...
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, env)
            if isinstance(node.op, ast.USub) and isinstance(
                v, (int, float)
            ):
                return -v
            if isinstance(node.op, ast.Not) and isinstance(v, bool):
                return not v
            return _combine(v)
        if isinstance(node, ast.BinOp):
            a = self.eval(node.left, env)
            b = self.eval(node.right, env)
            if isinstance(a, (int, float)) and isinstance(b, (int, float)):
                return self._arith(node.op, a, b)
            return _combine(a, b)
        if isinstance(node, ast.BoolOp):
            vals = [self.eval(v, env) for v in node.values]
            if all(isinstance(v, bool) for v in vals):
                return (
                    all(vals)
                    if isinstance(node.op, ast.And)
                    else any(vals)
                )
            return _combine(*vals)
        if isinstance(node, ast.Compare):
            if len(node.ops) != 1:
                raise _Unsupported("chained comparison")
            a = self.eval(node.left, env)
            b = self.eval(node.comparators[0], env)
            if isinstance(a, (int, float)) and isinstance(b, (int, float)):
                return self._cmp(node.ops[0], a, b)
            return _combine(a, b)
        if isinstance(node, ast.Call):
            return self.eval_call(node, env)
        if isinstance(node, ast.Subscript):
            return self.eval_subscript(node, env)
        if isinstance(node, ast.IfExp):
            c = self.eval(node.test, env)
            if isinstance(c, bool):
                return self.eval(node.body if c else node.orelse, env)
            return _combine(
                self.eval(node.body, env), self.eval(node.orelse, env)
            )
        if isinstance(node, ast.Lambda):
            return FuncVal(node, dict(env))
        raise _Unsupported(f"expression {ast.dump(node)[:60]}")

    @staticmethod
    def _arith(op: ast.operator, a, b):
        if isinstance(op, ast.Add):
            return a + b
        if isinstance(op, ast.Sub):
            return a - b
        if isinstance(op, ast.Mult):
            return a * b
        if isinstance(op, ast.FloorDiv):
            return a // b
        if isinstance(op, ast.Mod):
            return a % b
        if isinstance(op, ast.LShift):
            return a << b
        if isinstance(op, ast.BitAnd):
            return a & b
        if isinstance(op, ast.BitOr):
            return a | b
        raise _Unsupported(f"arithmetic op {op}")

    @staticmethod
    def _cmp(op: ast.cmpop, a, b):
        if isinstance(op, ast.Eq):
            return a == b
        if isinstance(op, ast.NotEq):
            return a != b
        if isinstance(op, ast.Lt):
            return a < b
        if isinstance(op, ast.LtE):
            return a <= b
        if isinstance(op, ast.Gt):
            return a > b
        if isinstance(op, ast.GtE):
            return a >= b
        raise _Unsupported(f"comparison op {op}")

    def eval_subscript(self, node: ast.Subscript, env: Dict[str, Any]):
        base = self.eval(node.value, env)
        if isinstance(base, Buf):
            index = self._eval_index(node.slice, env)
            return self.read_buf(base, index)
        if isinstance(base, tuple):
            idx = self.eval(node.slice, env)
            if isinstance(idx, int):
                return base[idx]
            raise _Unsupported("non-constant tuple index")
        # value[:, None], value[0], ... — row structure is preserved for the
        # patterns the kernels use; treat as passthrough
        return _combine(base)

    def _eval_index(self, node: ast.AST, env: Dict[str, Any]) -> Any:
        """Evaluate a subscript index into Ellipsis / DS / int / tuple."""
        if isinstance(node, ast.Constant) and node.value is Ellipsis:
            return Ellipsis
        if isinstance(node, ast.Tuple):
            return tuple(self._eval_index(e, env) for e in node.elts)
        if isinstance(node, ast.Slice):
            if node.lower is None and node.upper is None:
                return slice(None)
            raise _Unsupported("bounded python slice on a ref")
        return self.eval(node, env)

    def eval_call(self, node: ast.Call, env: Dict[str, Any]) -> Any:
        dotted = self._dotted(node.func)
        args = [self.eval(a, env) for a in node.args]
        kwargs = {
            k.arg: self.eval(k.value, env)
            for k in node.keywords
            if k.arg is not None
        }
        if dotted == "pl.program_id":
            axis = args[0]
            if not isinstance(axis, int) or axis >= len(self.pids):
                raise _Unsupported(f"pl.program_id({axis!r})")
            return self.pids[axis]
        if dotted == "pl.ds":
            if not all(isinstance(a, (int, bool)) for a in args):
                raise _Unsupported("pl.ds with non-concrete bounds")
            return DS(args[0], args[1])
        if dotted == "jax.lax.fori_loop":
            lo, hi, fn, carry = args
            if not (
                isinstance(lo, int)
                and isinstance(hi, int)
                and isinstance(fn, FuncVal)
            ):
                raise _Unsupported("non-concrete fori_loop")
            for j in range(lo, hi):
                carry = self.call_func(fn, [j, carry])
            return carry
        if dotted in ("jax.lax.broadcasted_iota",):
            return VALID
        if dotted is not None and dotted.split(".")[-1] in self.functions:
            fn = self.functions[dotted.split(".")[-1]]
            return self.call_func(FuncVal(fn, {}), args)
        if isinstance(node.func, ast.Name) and isinstance(
            env.get(node.func.id), FuncVal
        ):
            return self.call_func(env[node.func.id], args)
        if dotted is not None and (
            dotted.startswith("jnp.") or dotted.startswith("np.")
        ):
            # elementwise / broadcast / gather ops: validity-meet of array
            # args (take_along_axis is within-row, so row-aligned)
            return _combine(*args, *kwargs.values())
        if dotted is not None and dotted.split(".")[0] in ("COST_DT",):
            return VALID
        # casting calls like jnp.float32(x) are caught above; a module
        # constant used as a cast (COST_DT(x)) would land here
        base = self.eval(node.func, env) if dotted is None else None
        if base is VALID or base is None and dotted is not None:
            return _combine(*args)
        raise _Unsupported(f"call to {dotted or ast.dump(node.func)[:40]}")

    def call_func(self, fv: FuncVal, args: List[Any]) -> Any:
        node = fv.node
        if isinstance(node, ast.Lambda):
            params = [a.arg for a in node.args.args]
            env = dict(fv.env)
            env.update(zip(params, args))
            # defaults (the _n=nd idiom) for unsupplied trailing params
            defaults = node.args.defaults
            if defaults:
                names = params[len(params) - len(defaults):]
                for name, d in zip(names, defaults):
                    if name not in env or len(args) < len(params):
                        env.setdefault(name, self.eval(d, fv.env))
            return self.eval(node.body, env)
        params = [a.arg for a in node.args.args]
        if len(args) != len(params):
            raise _Unsupported(
                f"call arity mismatch for {node.name}"
            )
        env = dict(fv.env)
        env.update(zip(params, args))
        return self.exec_body(node.body, env)

    # -- statements --------------------------------------------------------

    def exec_body(self, body: Sequence[ast.stmt], env: Dict[str, Any]):
        for stmt in body:
            if isinstance(stmt, ast.Return):
                if stmt.value is None:
                    return None
                return self.eval(stmt.value, env)
            self.exec_stmt(stmt, env)
        return None

    def exec_stmt(self, stmt: ast.stmt, env: Dict[str, Any]) -> None:
        if isinstance(stmt, ast.Expr):
            if isinstance(stmt.value, ast.Constant):
                return  # docstring
            self.eval(stmt.value, env)
            return
        if isinstance(stmt, ast.Assign):
            value = self.eval(stmt.value, env)
            for target in stmt.targets:
                self.assign(target, value, env)
            return
        if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self.assign(stmt.target, self.eval(stmt.value, env), env)
            return
        if isinstance(stmt, ast.If):
            test = self.eval(stmt.test, env)
            if not isinstance(test, bool):
                raise _Unsupported("data-dependent python `if` in kernel")
            self.exec_many(stmt.body if test else stmt.orelse, env)
            return
        if isinstance(stmt, ast.FunctionDef):
            guard = None
            for dec in stmt.decorator_list:
                dotted = (
                    self._dotted(dec.func)
                    if isinstance(dec, ast.Call)
                    else None
                )
                if dotted == "pl.when":
                    guard = self.eval(dec.args[0], env)
                else:
                    raise _Unsupported(
                        f"decorator on {stmt.name} is not pl.when"
                    )
            if stmt.decorator_list:
                if not isinstance(guard, bool):
                    raise _Unsupported(
                        f"pl.when({stmt.name}) guard is not concrete"
                    )
                if guard:
                    self.exec_many(stmt.body, dict(env))
            else:
                env[stmt.name] = FuncVal(stmt, dict(env))
            return
        raise _Unsupported(f"statement {type(stmt).__name__}")

    def exec_many(self, body: Sequence[ast.stmt], env: Dict[str, Any]):
        for stmt in body:
            self.exec_stmt(stmt, env)

    def assign(self, target: ast.AST, value: Any, env: Dict[str, Any]):
        if isinstance(target, ast.Name):
            env[target.id] = value
            return
        if isinstance(target, ast.Tuple):
            if not isinstance(value, tuple) or len(value) != len(
                target.elts
            ):
                raise _Unsupported("tuple-unpack arity mismatch")
            for t, v in zip(target.elts, value):
                self.assign(t, v, env)
            return
        if isinstance(target, ast.Subscript):
            base = self.eval(target.value, env)
            if not isinstance(base, Buf):
                raise _Unsupported("subscript-assign to a non-ref")
            index = self._eval_index(target.slice, env)
            self.write_buf(base, index, value)
            return
        raise _Unsupported(f"assign target {type(target).__name__}")


# -- module loading ----------------------------------------------------------


def _load_module(path: str) -> Tuple[Dict[str, Any], Dict[str, ast.FunctionDef]]:
    """Parse a kernel source file: module-level functions + evaluable
    integer/float constants (e.g. ``_INT_CLAMP = 1 << 30``)."""
    with open(path, "r", encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    functions: Dict[str, ast.FunctionDef] = {}
    consts: Dict[str, Any] = {}
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            functions[node.name] = node
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if isinstance(tgt, ast.Name):
                try:
                    consts[tgt.id] = ast.literal_eval(node.value)
                except (ValueError, TypeError, SyntaxError):
                    try:
                        consts[tgt.id] = _const_fold(node.value)
                    except _Unsupported:
                        consts[tgt.id] = VALID
    return consts, functions


def _const_fold(node: ast.AST) -> Any:
    if isinstance(node, ast.Constant):
        return node.value
    if isinstance(node, ast.BinOp):
        a, b = _const_fold(node.left), _const_fold(node.right)
        if isinstance(a, (int, float)) and isinstance(b, (int, float)):
            return _Interp._arith(node.op, a, b)
    raise _Unsupported("non-constant module assignment")


# -- the fused-kernel harness ------------------------------------------------

# parameter-name → role convention shared by the shipped kernels and the
# test fixtures (names are the contract; unknown names fail closed)
_FUSED_TABLE_INPUTS = ("t0", "t0b", "t0e")
_FUSED_VEC_INPUTS = ("wa", "wb", "cum", "uf", "ub", "toff", "tpre")
_FUSED_MAT_INPUTS = ("mn", "ma")
_FUSED_TABLES = ("t", "tb", "te")  # carried outputs checked for validity
_FUSED_SCRATCH = ("r", "lm", "lmb", "lme", "lmb3")  # carried, unchecked


@dataclasses.dataclass(frozen=True)
class FusedCase:
    L: int
    BR: int
    allow_fall: bool = True
    host_on: bool = False

    def describe(self) -> str:
        return (
            f"L={self.L} BR={self.BR} allow_fall={self.allow_fall}"
            + (f" host_on={self.host_on}" if self.host_on else "")
        )


DEFAULT_FUSED_CASES: Tuple[FusedCase, ...] = tuple(
    FusedCase(L, BR, af)
    for L in (1, 2, 3, 5)
    for BR in (1, 2, 3)
    for af in (False, True)
    if BR <= max(L, 1)
)


def _fused_contract(case: FusedCase) -> Dict[str, Any]:
    """Shapes and concrete offsets, mirrored from ``ops._FusedOperands``."""
    L, BR = case.L, case.BR
    sizes = [L + 1 - d for d in range(L + 1)]
    off = [0]
    for s in sizes:
        off.append(off[-1] + s)
    ncells = off[-1]
    nrows = ncells + 2 * L + BR
    vec = 2 * L + BR + 2
    rt = -(-max(L, 1) // BR)
    return {
        "off": off,
        "ncells": ncells,
        "nrows": nrows,
        "vec": vec,
        "rt": rt,
        "W": 4,  # columns are untracked; any width >= 2 works
    }


def _make_fused_bufs(
    kernel: ast.FunctionDef, case: FusedCase, contract: Dict[str, Any]
) -> Tuple[Dict[str, Buf], List[Buf]]:
    L = case.L
    nrows, vec, rt = contract["nrows"], contract["vec"], contract["rt"]
    W = contract["W"]
    bufs: Dict[str, Buf] = {}
    tables: List[Buf] = []
    base_valid = [i < L + 1 for i in range(nrows)]  # band 0 is real
    for p in kernel.args.args:
        name = p.arg
        if not name.endswith("_ref"):
            raise _Unsupported(f"positional param {name!r} is not a ref")
        short = name[:-4]
        if short in _FUSED_TABLE_INPUTS:
            bufs[name] = Buf(
                name, (nrows, W), readonly=True, valid=list(base_valid)
            )
        elif short == "off":
            bufs[name] = Buf(
                name,
                (len(contract["off"]),),
                readonly=True,
                values=list(contract["off"]),
            )
        elif short in _FUSED_VEC_INPUTS:
            bufs[name] = Buf(name, (vec,), readonly=True)
        elif short in _FUSED_MAT_INPUTS:
            bufs[name] = Buf(
                name, (max(L, 1), rt * case.BR), readonly=True
            )
        elif short in _FUSED_TABLES:
            b = Buf(
                name, (nrows, W), readonly=False, valid=[False] * nrows
            )
            bufs[name] = b
            tables.append(b)
        elif short in _FUSED_SCRATCH:
            bufs[name] = Buf(
                name, (nrows, W), readonly=False, valid=[False] * nrows
            )
        else:
            raise _Unsupported(
                f"parameter {name!r} outside the dp_fill name contract"
            )
    return bufs, tables


def analyze_fused_kernel(
    path: str,
    kernel_name: str,
    cases: Sequence[FusedCase] = DEFAULT_FUSED_CASES,
    offload: bool = False,
) -> List[KernelIssue]:
    """Run the lattice interpreter over one fused kernel for every case;
    returns all issues (empty = machine-checked safe on the case matrix)."""
    consts, functions = _load_module(path)
    if kernel_name not in functions:
        return [
            KernelIssue(
                kernel_name, "unsupported", f"kernel not found in {path}"
            )
        ]
    kernel = functions[kernel_name]
    issues: List[KernelIssue] = []
    all_cases = list(cases)
    if offload:
        all_cases = [
            dataclasses.replace(c, host_on=h)
            for c in cases
            for h in (False, True)
        ]
    for case in all_cases:
        contract = _fused_contract(case)
        interp = _Interp(
            dict(consts), functions, issues, kernel_name, case.describe()
        )
        try:
            bufs, tables = _make_fused_bufs(kernel, case, contract)
            env: Dict[str, Any] = dict(bufs)
            for kw in kernel.args.kwonlyargs:
                name = kw.arg
                env[name] = {
                    "L": case.L,
                    "W": contract["W"],
                    "BR": case.BR,
                    "allow_fall": case.allow_fall,
                    "host_on": case.host_on,
                }.get(name)
                if env[name] is None:
                    raise _Unsupported(f"unknown kw-only param {name!r}")
            rt = contract["rt"]
            before = len(issues)
            for pd in range(case.L):  # band dim, outer
                for pi in range(rt):  # row tiles, innermost (sequential)
                    interp.pids = (pd, pi)
                    interp.exec_many(kernel.body, dict(env))
            for tb in tables:
                assert tb.valid is not None
                bad = [
                    r
                    for r in range(contract["ncells"])
                    if not tb.valid[r]
                ]
                if bad:
                    interp.issue(
                        "final-invalid",
                        f"{len(bad)} real row(s) of {tb.name} end the "
                        f"grid with garbage lanes (first: {bad[:4]})",
                    )
            del before
        except _Unsupported as e:
            issues.append(
                KernelIssue(
                    kernel_name,
                    "unsupported",
                    str(e),
                    case.describe(),
                )
            )
        except _IssueStop:
            pass
    return issues


# -- the per-band harness ----------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BandCase:
    nt: int  # row tiles
    d: int   # splits (innermost grid dim)
    BR: int = 2

    def describe(self) -> str:
        return f"nt={self.nt} d={self.d} BR={self.BR}"


DEFAULT_BAND_CASES: Tuple[BandCase, ...] = (
    BandCase(1, 1),
    BandCase(2, 2),
    BandCase(3, 3),
    BandCase(2, 4),
)


def _extract_pallas_call(
    wrapper: ast.FunctionDef,
) -> Tuple[ast.Call, Dict[str, ast.expr]]:
    assigns: Dict[str, ast.expr] = {}
    found: Optional[ast.Call] = None
    for node in ast.walk(wrapper):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt = node.targets[0]
            if isinstance(tgt, ast.Name):
                assigns[tgt.id] = node.value
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "pallas_call":
                found = node
            elif isinstance(fn, ast.Call):
                inner = fn.func
                if (
                    isinstance(inner, ast.Attribute)
                    and inner.attr == "pallas_call"
                ):
                    found = fn
    if found is None:
        raise _Unsupported("no pallas_call in wrapper")
    return found, assigns


def _resolve_specs(
    node: ast.expr, assigns: Dict[str, ast.expr]
) -> List[ast.Call]:
    """Resolve an ``out_specs`` expression to a list of BlockSpec calls."""
    seen = 0
    while isinstance(node, ast.Name) and node.id in assigns and seen < 5:
        node = assigns[node.id]
        seen += 1
    if isinstance(node, ast.List):
        out: List[ast.Call] = []
        for e in node.elts:
            out.extend(_resolve_specs(e, assigns))
        return out
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr == "BlockSpec":
            return [node]
    raise _Unsupported("out_specs is not a (list of) literal BlockSpec")


def analyze_band_kernel(
    path: str,
    wrapper_name: str,
    kernel_name: str,
    cases: Sequence[BandCase] = DEFAULT_BAND_CASES,
) -> List[KernelIssue]:
    """Check a per-band kernel + its driver's BlockSpecs: output index maps
    constant along the innermost (split) dim and row-disjoint across tiles,
    and the init/accumulate guard discipline actually initializes every
    output row before it is read (via the validity lattice)."""
    consts, functions = _load_module(path)
    issues: List[KernelIssue] = []
    if kernel_name not in functions or wrapper_name not in functions:
        return [
            KernelIssue(
                kernel_name,
                "unsupported",
                f"kernel/wrapper not found in {path}",
            )
        ]
    kernel = functions[kernel_name]
    for case in cases:
        interp = _Interp(
            dict(consts), functions, issues, kernel_name, case.describe()
        )
        try:
            call, assigns = _extract_pallas_call(functions[wrapper_name])
            out_specs_kw = next(
                (k.value for k in call.keywords if k.arg == "out_specs"),
                None,
            )
            if out_specs_kw is None:
                raise _Unsupported("pallas_call has no out_specs kwarg")
            specs = _resolve_specs(out_specs_kw, assigns)
            # evaluate each out index_map over the whole grid
            maps: List[List[List[int]]] = []  # [spec][i][origin-row]
            lam_env = {
                "block_rows": case.BR,
                "w": 4,
                "d": case.d,
                "ns_pad": case.nt * case.BR,
            }
            for spec in specs:
                if len(spec.args) < 2:
                    raise _Unsupported("BlockSpec without index_map")
                lam = spec.args[1]
                origins: List[List[int]] = []
                for i in range(case.nt):
                    row: List[int] = []
                    for j in range(case.d):
                        fv = FuncVal(lam, dict(lam_env))
                        got = interp.call_func(fv, [i, j])
                        if not (
                            isinstance(got, tuple)
                            and isinstance(got[0], int)
                        ):
                            raise _Unsupported(
                                "index_map origin is not concrete"
                            )
                        row.append(got[0])
                    origins.append(row)
                maps.append(origins)
            for si, origins in enumerate(maps):
                for i, row in enumerate(origins):
                    if any(o != row[0] for o in row):
                        interp.issue(
                            "grid-race",
                            f"out spec {si}: block origin varies along "
                            f"the innermost (split) dim at tile {i} — "
                            f"the accumulator is not revisited",
                        )
                firsts = [row[0] for row in origins]
                if len(set(firsts)) != len(firsts):
                    interp.issue(
                        "grid-race",
                        f"out spec {si}: row tiles alias "
                        f"(origins {firsts}) — writes are not disjoint",
                    )
            # lattice pass over the kernel body on the same grid
            nrows = case.nt * case.BR
            outs: List[Buf] = []
            bufs: Dict[str, Buf] = {}
            n_out = len(specs)
            params = [a.arg for a in kernel.args.args]
            for name in params[: len(params) - n_out]:
                bufs[name] = Buf(name, (nrows,), readonly=True)
            for k, name in enumerate(params[len(params) - n_out:]):
                b = Buf(
                    name,
                    (nrows,),
                    readonly=False,
                    valid=[False] * nrows,
                )
                bufs[name] = b
                outs.append(b)
            for i in range(case.nt):
                for j in range(case.d):
                    interp.pids = (i, j)
                    for k, b in enumerate(outs):
                        o = maps[k][i][j] * case.BR
                        b.window = (o, o + case.BR)
                    for name in params[: len(params) - n_out]:
                        bufs[name].window = (0, case.BR)
                    interp.exec_many(kernel.body, dict(bufs))
            for b in outs:
                assert b.valid is not None
                bad = [r for r in range(nrows) if not b.valid[r]]
                if bad:
                    interp.issue(
                        "final-invalid",
                        f"{len(bad)} row(s) of {b.name} never receive a "
                        f"valid write (first: {bad[:4]}) — the j==0 "
                        f"init is missing or reads the accumulator",
                    )
        except _Unsupported as e:
            issues.append(
                KernelIssue(
                    kernel_name, "unsupported", str(e), case.describe()
                )
            )
        except _IssueStop:
            pass
    return issues


# -- public entry points -----------------------------------------------------


def dp_fill_kernel_path() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(
        os.path.dirname(here), "kernels", "dp_fill", "kernel.py"
    )


def analyze_dp_fill(path: Optional[str] = None) -> List[KernelIssue]:
    """Analyze all four shipped dp_fill kernels (the CI gate)."""
    path = path or dp_fill_kernel_path()
    issues: List[KernelIssue] = []
    issues += analyze_band_kernel(
        path, "band_min_two_tier", "_band_min_kernel"
    )
    issues += analyze_band_kernel(
        path, "band_min_offload", "_band_min_offload_kernel"
    )
    issues += analyze_fused_kernel(path, "_fused_two_tier_kernel")
    issues += analyze_fused_kernel(
        path, "_fused_offload_kernel", offload=True
    )
    return issues


def cache_key() -> str:
    """Fingerprint of the solver + kernel sources — analysis results are
    valid exactly as long as this matches
    :func:`repro.core.solver_cache.code_fingerprint`."""
    from ..core.solver_cache import code_fingerprint

    return code_fingerprint()
