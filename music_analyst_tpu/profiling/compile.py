"""Compile-boundary introspection: :func:`profiled_jit`.

Wraps the jit lower/compile boundary the engines use so every compiled
program records what it costs before it ever runs.

**Recorded at every compile** (a :class:`CompileRecord`: the ``compile``
event, :func:`compile_records`, the manifest's ``profiling`` section):

* ``cost_analysis()`` — FLOPs and bytes-accessed per execution,
* ``memory_analysis()`` — temp/argument/output allocation bytes (TPU
  backends implement it; CPU returns nothing and the field stays null),
* an HLO fingerprint (sha256 of the lowered StableHLO text) so two runs
  can prove they executed the same program,
* the compile's seconds, a weight-quantized call's parameter bytes, and the
  ``attention_paths`` / ``traced_paths`` noted while the program was traced
  (:func:`note_attention_path`, :func:`note_traced_path`), and
* a **recompile detector**: calls are keyed on their abstract avals
  (shape/dtype of every array leaf + values of everything static); a new
  key after the first compile bumps ``profiling.recompiles`` and emits a
  ``recompile`` event naming the offending shape change — the telemetry
  answer to "why is this run spending its wall-clock in XLA".

**Derived when asked, and kept** (nothing at compile time, nothing in a
record, so no manifest or event grows and a run nobody traces pays
nothing):

* :func:`op_scopes` — for every executable this process holds, each
  instruction of the optimised program with the ``jax.named_scope`` path it
  was traced under, read out of ``compiled.as_text()``: what names the
  operations of a device trace (``profile_run`` writes it as
  ``op_scopes.json``; ``perfbench/scope_reduce.py`` sums a trace by it).

The wrapper is a fallback-safe veneer over ``jax.jit``: the AOT
``lower(...).compile()`` path feeds the records, and any AOT-ineligible
call pattern (donated buffers, weak types the executable rejects, …)
falls through to the plain jitted callable — numerics never depend on the
profiler.  Executables are *invoked* exactly as jit would; the wrapper
never waits on a result (synchronisation stays the caller's readback).
"""

from __future__ import annotations

import hashlib
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

# Process-lifetime registry of every ProfiledFunction, in creation order —
# the manifest's ``profiling`` section reads it at run exit.
_REGISTRY: List["ProfiledFunction"] = []
_REGISTRY_LOCK = threading.Lock()

# The attention paths chosen while the program a ProfiledFunction is
# compiling gets traced, per thread: {path: layers that took it}.
_TRACING = threading.local()


def note_attention_path(path: str) -> None:
    """Say, at trace time, which attention implementation a layer took
    (``models/layers.MultiHeadAttention``): bumps the counter
    ``attention.<path>`` and, when a :func:`profiled_jit` program is being
    compiled on this thread, lands in its record's ``attention_paths`` —
    so a run's manifest names the path of every compiled shape."""
    from music_analyst_tpu.telemetry import get_telemetry

    get_telemetry().count(f"attention.{path}")
    paths = getattr(_TRACING, "attention_paths", None)
    if paths is not None:
        paths[path] = paths.get(path, 0) + 1


def note_traced_path(path: str) -> None:
    """Say, at trace time, which form of a layer a program took where the
    shapes decide it (``mla.expanded`` / ``mla.absorbed``,
    ``moe.grouped``): bumps the counter ``traced.<path>`` and lands in the
    compile record's ``traced_paths`` ({path: layers that took it})."""
    from music_analyst_tpu.telemetry import get_telemetry

    get_telemetry().count(f"traced.{path}")
    paths = getattr(_TRACING, "traced_paths", None)
    if paths is not None:
        paths[path] = paths.get(path, 0) + 1


def _leaf_sig(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{dtype}{list(shape)}"
    return repr(leaf)


def _aval_key(args: tuple, kwargs: dict) -> str:
    """Abstract signature of a call: array leaves contribute shape/dtype,
    everything else (static ints, strings) its repr."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return ";".join(_leaf_sig(leaf) for leaf in leaves) + f"#{treedef}"


def _wq_param_bytes(args: tuple, kwargs: dict) -> Optional[Dict[str, int]]:
    """Param-bytes breakdown of the call's weight-quantized argument
    trees (stored int codes+scales vs the float bytes a dequantizing
    epilogue transiently touches), or ``None`` for all-float calls —
    the field only appears once quantization is actually in play."""
    try:
        from music_analyst_tpu.ops.quant import (
            QuantizedParam,
            param_tree_bytes,
        )

        def _has_qp(tree) -> bool:
            return any(
                isinstance(leaf, QuantizedParam)
                for leaf in jax.tree_util.tree_leaves(
                    tree, is_leaf=lambda x: isinstance(x, QuantizedParam)
                )
            )

        trees = [
            a for a in list(args) + list(kwargs.values()) if _has_qp(a)
        ]
        if not trees:
            return None
        return param_tree_bytes(trees)
    except Exception:
        return None


def _scalar(analysis: Any, key: str) -> Optional[float]:
    """Pull one metric out of ``cost_analysis()`` output, whose container
    type changed across jax versions (dict vs [dict])."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    value = analysis.get(key)
    try:
        return float(value) if value is not None else None
    except (TypeError, ValueError):
        return None


# ``  [ROOT ]%name = <shape> opcode(operands), attr=..., metadata={...}``
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?(\S+) = (.*)$")
_COMPUTATION = re.compile(r"^(ENTRY )?%?(\S+) \(.*\{$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND = re.compile(r"%([^\s,()]+)")
# The instructions whose computations run as operations of their own (a
# fused computation is one operation on the chip: not followed).
_FOLLOWED = ("while", "conditional", "call")
_CALLEE = re.compile(
    r"\b(?:condition|body|true_computation|false_computation|to_apply)"
    r"=%?([^\s,}]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def _balanced(text: str) -> int:
    """Where the parenthesis that opens ``text`` closes (-1: nowhere)."""
    depth = 0
    for i, char in enumerate(text):
        depth += (char == "(") - (char == ")")
        if depth == 0:
            return i
    return -1


def _opcode_and_operands(rest: str) -> Tuple[str, List[str]]:
    """From an instruction's text behind ``name = ``: skip the result shape
    (one word, or a tuple in parentheses), then ``opcode(%a, %b)``."""
    behind = (rest[_balanced(rest) + 1:] if rest.startswith("(")
              else rest.partition(" ")[2])
    found = _OPCODE.match(behind)
    if not found:
        return "", []
    operands = behind[found.end() - 1:]
    return found.group(1), _OPERAND.findall(
        operands[:_balanced(operands) + 1])


def _computations(text: str):
    """``(module name, entry computation, {computation: [(instruction,
    its text behind "name = ")]})`` of a module's text."""
    module = ""
    computations: Dict[str, List[Tuple[str, str]]] = {}
    entry, current = None, None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        head = _COMPUTATION.match(line)
        if head:
            current = computations.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(line) if current is not None else None
        if found:
            current.append((found.group(1), found.group(2)))
    return module, entry, computations


def _is_path(op_name: str) -> bool:
    """Whether an ``op_name`` is the path an operation was traced under
    (every program here is a ``jax.jit``'s) and not an argument's name or a
    name of XLA's own."""
    return op_name.startswith("jit(")


def hlo_op_scopes(text: str) -> Tuple[str, Dict[str, List[Optional[str]]]]:
    """``(module name, {instruction: [op_name, enclosing instruction]})``
    from an optimised module's text (``compiled.as_text()``): the
    instructions of the entry computation and of every computation reached
    from it as a ``while`` body or condition, a ``conditional`` branch or a
    ``call``.  The enclosing instruction is the ``while`` / ``conditional``
    / ``call`` whose computation holds it, ``None`` in the entry.

    ``op_name`` is whole as XLA printed it where that is the path the
    instruction was traced under (``jit(f)/prefill/mla/dot_general``).
    Where XLA printed none (the copies it adds, tuples) or a name of its
    own (the TPU compiler's grouped-matmul kernels are all
    ``ragged-dot-none``), the instruction stands under the path of its
    first operand that has one, else of its enclosing instruction, else of
    its first user (a weight's relayout before the projection that reads
    it), with XLA's own name, if any, as the last component
    (``jit(f)/.../moe.dispatch/gather/ragged-dot-none``).  A parameter, and
    what nothing leads to a path from, keeps what XLA printed."""
    module, entry, computations = _computations(text)
    ops: Dict[str, List[Optional[str]]] = {}
    users: Dict[str, List[str]] = {}
    parameters = set()  # the entry's: they keep their argument's name

    def under(name: str, others: List[Optional[str]]) -> None:
        paths = [ops[o][0] for o in others
                 if o in ops and _is_path(ops[o][0])]
        if paths and not _is_path(ops[name][0]):
            ops[name][0] = "/".join(filter(None, (paths[0], ops[name][0])))

    todo = [(entry, None)] if entry is not None else []
    seen = set()
    while todo:
        computation, enclosing = todo.pop()
        if computation in seen:
            continue
        seen.add(computation)
        for name, rest in computations.get(computation, ()):
            printed = _OP_NAME.search(rest)
            opcode, operands = _opcode_and_operands(rest)
            ops[name] = [printed.group(1) if printed else "", enclosing]
            if opcode == "parameter" and enclosing is None:
                parameters.add(name)
                continue
            # operands stand before their users in the text
            under(name, operands + [enclosing])
            for operand in operands:
                users.setdefault(operand, []).append(name)
            if opcode not in _FOLLOWED:
                continue
            callees = _CALLEE.findall(rest)
            for branches in _BRANCHES.findall(rest):
                callees += [b.strip().lstrip("%")
                            for b in branches.split(",")]
            todo += [(callee, name) for callee in callees]
    for name in reversed(list(ops)):  # users first, then their operands
        if name not in parameters:
            under(name, users.get(name, []))
    return module, ops


class CompileRecord:
    """One compiled program's cost/memory/fingerprint digest."""

    __slots__ = (
        "name", "aval_key", "flops", "bytes_accessed", "temp_bytes",
        "argument_bytes", "output_bytes", "hlo_fingerprint",
        "compile_seconds", "param_bytes", "attention_paths", "traced_paths",
    )

    def __init__(self, name: str, aval_key: str) -> None:
        self.name = name
        self.aval_key = aval_key
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.temp_bytes: Optional[int] = None
        self.argument_bytes: Optional[int] = None
        self.output_bytes: Optional[int] = None
        self.hlo_fingerprint: Optional[str] = None
        self.compile_seconds: float = 0.0
        # Weight-quantized calls only: stored vs dequant-transient bytes
        # of the argument param tree (ops.quant.param_tree_bytes).
        self.param_bytes: Optional[Dict[str, int]] = None
        # {path: layers} noted while this program was traced
        # (note_attention_path); empty for a program without attention.
        self.attention_paths: Dict[str, int] = {}
        # {path: layers} noted by note_traced_path; empty for most programs.
        self.traced_paths: Dict[str, int] = {}

    def as_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "aval_key": self.aval_key,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "temp_bytes": self.temp_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "hlo_fingerprint": self.hlo_fingerprint,
            "compile_seconds": round(self.compile_seconds, 6),
            "param_bytes": self.param_bytes,
            "attention_paths": self.attention_paths,
            "traced_paths": self.traced_paths,
        }


class ProfiledFunction:
    """A jitted callable whose compiles are observed and keyed on avals."""

    def __init__(self, fn: Callable, name: Optional[str] = None,
                 **jit_kwargs: Any) -> None:
        self._fn = fn
        self.name = name or getattr(fn, "__name__", None) or "jit_fn"
        self._jit = jax.jit(fn, **jit_kwargs)
        # Static arguments given by keyword key the compile like every
        # other argument and are left out of the call of the executable,
        # which was specialised on them (``jax.stages.Compiled``).
        static = jit_kwargs.get("static_argnames") or ()
        self._static = frozenset(
            (static,) if isinstance(static, str) else static)
        self._lock = threading.Lock()
        self._compiled: Dict[str, Any] = {}  # aval_key -> executable | None
        self._op_scopes: Dict[str, Any] = {}  # aval_key -> op_scopes() entry
        self.records: Dict[str, CompileRecord] = {}
        with _REGISTRY_LOCK:
            _REGISTRY.append(self)

    # -------------------------------------------------------- introspection

    def _record(self, key: str, lowered: Any, compiled: Any,
                seconds: float) -> CompileRecord:
        rec = CompileRecord(self.name, key)
        rec.compile_seconds = seconds
        try:
            rec.hlo_fingerprint = hashlib.sha256(
                lowered.as_text().encode()
            ).hexdigest()[:16]
        except Exception:
            pass
        try:
            cost = compiled.cost_analysis()
            rec.flops = _scalar(cost, "flops")
            rec.bytes_accessed = _scalar(cost, "bytes accessed")
        except Exception:
            pass
        try:
            mem = compiled.memory_analysis()
            rec.temp_bytes = int(mem.temp_size_in_bytes)
            rec.argument_bytes = int(mem.argument_size_in_bytes)
            rec.output_bytes = int(mem.output_size_in_bytes)
        except Exception:
            pass  # CPU PJRT has no memory_analysis — fields stay null
        return rec

    def _compile_for(self, key: str, args: tuple, kwargs: dict) -> Any:
        """AOT-compile for this aval key; record + count; None on failure."""
        from music_analyst_tpu.telemetry import get_telemetry

        tel = get_telemetry()
        try:
            from music_analyst_tpu.observability import watchdog
            from music_analyst_tpu.resilience.faults import fault_point
            from music_analyst_tpu.resilience.policy import RetryPolicy

            attention_paths: Dict[str, int] = {}
            traced_paths: Dict[str, int] = {}

            def _lower_and_compile():
                fault_point("compile.first", fn=self.name)
                attention_paths.clear()
                traced_paths.clear()
                _TRACING.attention_paths = attention_paths
                _TRACING.traced_paths = traced_paths
                try:
                    low = self._jit.lower(*args, **kwargs)
                finally:
                    _TRACING.attention_paths = None
                    _TRACING.traced_paths = None
                return low, low.compile()

            t0 = time.perf_counter()
            # First compiles are the classic silent-hang site; a
            # watchdog trip here reads compile_hang.  Transient failures
            # (an injected compile.first fault) get re-attempted; a
            # persistent one falls through to
            # the plain-jit path below — degraded introspection, same
            # results.
            with watchdog.watch(f"compile:{self.name}", kind="compile"):
                lowered, compiled = RetryPolicy(base_s=0.05, cap_s=1.0).call(
                    _lower_and_compile, site="compile.first"
                )
            seconds = time.perf_counter() - t0
        except Exception as exc:
            # Not AOT-eligible (or the backend refused): the plain jit
            # call still compiles and runs; we just lose the record.
            tel.event("compile_introspection_failed", fn=self.name,
                      error=str(exc)[:200])
            return None
        rec = self._record(key, lowered, compiled, seconds)
        rec.param_bytes = _wq_param_bytes(args, kwargs)
        rec.attention_paths = attention_paths
        rec.traced_paths = traced_paths
        prior = list(self.records)
        self.records[key] = rec
        tel.count("profiling.compiles")
        attrs = rec.as_dict()
        attrs["fn"] = attrs.pop("name")  # "name" is the event name itself
        tel.event("compile", **attrs)
        if prior:
            # Same function, new avals: that is THE recompile signature —
            # log old→new so the offending shape change is one grep away.
            tel.count("profiling.recompiles")
            tel.event(
                "recompile", fn=self.name, prev_aval=prior[-1],
                new_aval=key, n_variants=len(prior) + 1,
            )
        return compiled

    # --------------------------------------------------------------- call

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        # Called under an outer trace (jit-of-jit): no concrete inputs to
        # AOT-compile against — defer to plain jit, which inlines.
        if any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree_util.tree_leaves((args, kwargs))
        ):
            return self._jit(*args, **kwargs)
        key = _aval_key(args, kwargs)
        with self._lock:
            known = key in self._compiled
            executable = self._compiled.get(key)
        if not known:
            executable = self._compile_for(key, args, kwargs)
            with self._lock:
                self._compiled[key] = executable
        if executable is not None:
            try:
                return executable(*args, **{
                    name: value for name, value in kwargs.items()
                    if name not in self._static})
            except Exception:
                # Executable/argument mismatch (layout, weak type, …):
                # permanently fall back for this key.
                with self._lock:
                    self._compiled[key] = None
        return self._jit(*args, **kwargs)

    def op_scopes(self) -> List[Dict[str, Any]]:
        """This function's entries of :func:`op_scopes`, one a compiled
        shape; an executable's text is read once."""
        with self._lock:
            compiled = dict(self._compiled)
        out = []
        for key, executable in compiled.items():
            if executable is None:  # fell back to plain jit: no text
                out.append({"fn": self.name, "aval_key": key,
                            "module": None, "ops": None})
                continue
            if key not in self._op_scopes:
                module, ops = hlo_op_scopes(executable.as_text())
                self._op_scopes[key] = {"fn": self.name, "aval_key": key,
                                        "module": module, "ops": ops}
            out.append(self._op_scopes[key])
        return out

    # Parity helpers so a ProfiledFunction drops in where jax.jit was.
    def lower(self, *args: Any, **kwargs: Any):
        return self._jit.lower(*args, **kwargs)

    def trace(self, *args: Any, **kwargs: Any):
        return self._jit.trace(*args, **kwargs)

    def _cache_size(self) -> int:
        """Compiled-variant count (jit cache + AOT executables): the
        no-retrace tests assert this stays flat across repeat calls."""
        with self._lock:
            aot = len(self._compiled)
        try:
            return self._jit._cache_size() + aot
        except Exception:
            return aot


def profiled_jit(fn: Callable, name: Optional[str] = None,
                 **jit_kwargs: Any) -> ProfiledFunction:
    """``jax.jit`` with compile introspection + recompile detection.

    Drop-in at the engines' jit boundaries; see the module docstring for
    what each compile records.  ``jit_kwargs`` pass through to ``jax.jit``
    (``static_argnames``, ``out_shardings``, …).
    """
    return ProfiledFunction(fn, name=name, **jit_kwargs)


def compile_records() -> List[Dict[str, Any]]:
    """Every CompileRecord in this process, in compile order per function.

    Process-lifetime (memoized engine callables outlive a single run), so
    the manifest labels it accordingly.
    """
    with _REGISTRY_LOCK:
        fns = list(_REGISTRY)
    out: List[Dict[str, Any]] = []
    for fn in fns:
        out.extend(rec.as_dict() for rec in fn.records.values())
    return out


def op_scopes() -> List[Dict[str, Any]]:
    """Which scope each device operation belongs to, for every executable
    :func:`profiled_jit` holds in this process: a list of ``{"fn", "aval_key",
    "module", "ops"}``.  ``module`` is the program's name as a device trace
    prints it (``HloModule jit__score_labels`` -> ``jit__score_labels``) and
    ``ops`` is :func:`hlo_op_scopes`'s ``{instruction: [op_name, enclosing
    instruction or None]}``.  A shape that fell back to plain ``jit``
    (``compile_introspection_failed``) is listed with ``module`` and ``ops``
    ``None``: its time in a trace is unmapped, not absent.

    Computed from ``compiled.as_text()`` when asked, not when compiling,
    and kept: a run that nobody traces never pays for it.  The paths are
    the executable's own: one loaded from the persistent compilation cache
    (its key leaves metadata out) carries the scopes of the tree that
    compiled it.
    """
    with _REGISTRY_LOCK:
        fns = list(_REGISTRY)
    out: List[Dict[str, Any]] = []
    for fn in fns:
        out.extend(fn.op_scopes())
    return out
