"""The repro-lint rule set: this repo's performance contracts, as AST checks.

Each rule encodes an invariant the paper's wins depend on (see
``docs/static_analysis.md`` for the catalog and the incident each rule is
grounded in). Rules are pure AST/static checks — no jax import, no
execution — so the CI lint job runs in seconds.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from . import astutil as A
from .engine import Diagnostic, Project, Rule, SourceFile

# Repo-relative path patterns. The linter is normally invoked from the
# repo root as ``python -m tools.repro_lint src tests`` so relpaths look
# like ``src/repro/serve/engine.py``; globs are written to also match
# fixture trees rooted elsewhere (``*serve/engine.py``).
TESTS = ("*tests/*", "*test_*.py", "*conftest.py")


# ---------------------------------------------------------------------------
# R1 host-sync-in-hot-path
# ---------------------------------------------------------------------------

_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_SYNC_METHODS = {"block_until_ready", "item"}
# host->device transfers: in the serving/training hot path every
# device<->host crossing must be one of the audited points — the KV
# tier / disagg hops go through serve/tier's staged_get/staged_put
# (ISSUE 9); a raw device_put elsewhere is an unaccounted PCIe hop
_TRANSFER_CALLS = {"jax.device_put"}


class HostSyncRule(Rule):
    """R1: no host synchronization on the serving/training hot path.

    ``jax.device_get`` / ``block_until_ready`` / ``.item()`` force a
    device->host round trip; one stray call inside the decode chunk loop or
    the train step turns the paper's "one dispatch per chunk" contract into
    one *sync* per token. ``jax.device_put`` is the same hop in the other
    direction: tier/disagg transfers must go through the staged-transfer
    helper (``serve/tier.staged_get``/``staged_put``, the audited §4.5
    crossing points), so a raw ``device_put`` in a hot-path module is
    flagged too. Additionally, ``float()``/``int()`` applied inside a
    ``lax.scan``/``fori_loop``/``while_loop`` body (anywhere, not just hot
    modules) would force concretization of a traced value at trace time.
    The engine's single per-chunk sync and the staged tier/disagg hops are
    the allowlisted dispatch points — waived inline with justification.
    """

    name = "R1-host-sync"
    doc = ("host sync (device_get/device_put/block_until_ready/.item, "
           "float/int on scan-traced values) in serve/train hot paths")
    include = ("*serve/*.py", "*train/trainer.py", "*train/fault.py",
               "*parallel/overlap.py")
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        for call in A.walk_calls(src.tree):
            name = A.call_name(call)
            if name in _SYNC_CALLS:
                out.append(self.diag(
                    src, call,
                    f"host sync `{name}` in a hot-path module; move it to "
                    "the per-chunk dispatch point or waive with the reason"))
            elif name in _TRANSFER_CALLS:
                out.append(self.diag(
                    src, call,
                    f"raw `{name}` in a hot-path module; tier/disagg "
                    "host hops must go through serve/tier's staged-"
                    "transfer helper (staged_get/staged_put) or waive "
                    "with the reason this crossing is audited"))
            elif isinstance(call.func, ast.Attribute) and \
                    call.func.attr in _SYNC_METHODS and not call.args:
                out.append(self.diag(
                    src, call,
                    f"host sync `.{call.func.attr}()` in a hot-path module"))
        return out

    def check_project(self, project: Project) -> Iterable[Diagnostic]:
        # float()/int()/.item() inside scan bodies: checked everywhere,
        # because a scan body is a traced scope no matter which module
        # defines it.
        out: List[Diagnostic] = []
        for src in project.files:
            # hot-path modules are fully covered by check_file; here we
            # only sweep scan bodies in the rest of the tree (tests excl.)
            if self.applies(src.rel) or not self._outside_tests(src):
                continue
            bodies = A.scan_body_functions(src.tree)
            if not bodies:
                continue
            parents = A.enclosing_map(src.tree)
            for node in A.nodes_in_functions(src.tree, bodies, parents):
                if not isinstance(node, ast.Call):
                    continue
                name = A.call_name(node)
                if name in ("float", "int") and node.args and not \
                        isinstance(node.args[0], ast.Constant):
                    out.append(Diagnostic(
                        src.rel, node.lineno, self.name,
                        f"`{name}()` on a value inside a scan/loop body "
                        "concretizes a traced value at trace time"))
                elif name in _SYNC_CALLS or (
                        isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_METHODS
                        and not node.args):
                    out.append(Diagnostic(
                        src.rel, node.lineno, self.name,
                        "host sync inside a scan/loop body can never run "
                        "under trace"))
        return out

    @staticmethod
    def _outside_tests(src: SourceFile) -> bool:
        import fnmatch
        return not any(fnmatch.fnmatch(src.rel, p) for p in TESTS)


# ---------------------------------------------------------------------------
# R2 jit-contract
# ---------------------------------------------------------------------------


class JitContractRule(Rule):
    """R2: hot-path ``jax.jit`` calls must declare buffer intent.

    In the engine/trainer, jitted entry points round-trip multi-GB cache or
    optimizer buffers every dispatch. Donation (``donate_argnums``) is what
    keeps that in-place; on meshed engines, pinned ``out_shardings`` is
    what keeps GSPMD from handing back a re-sharded cache whose new input
    sharding would retrace the next dispatch (the compile-once trace-count
    contract in ``tests/test_serve_fused.py``). A jit that genuinely has
    nothing to donate gets an inline waiver saying why.
    """

    name = "R2-jit-contract"
    doc = ("hot-path jax.jit must pass donate_argnums (and out_shardings "
           "in the meshed engine) or carry a justified waiver")
    include = ("*serve/engine.py", "*serve/tier.py", "*serve/disagg.py",
               "*train/trainer.py")
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        meshed_engine = src.rel.endswith(("serve/engine.py",
                                          "serve/tier.py"))
        for call in A.walk_calls(src.tree):
            if A.call_name(call).rsplit(".", 1)[-1] != "jit":
                continue
            if not A.call_name(call).startswith(("jax.", "jit")):
                continue
            kw = A.keyword_map(call)
            if "donate_argnums" not in kw and "donate_argnames" not in kw:
                out.append(self.diag(
                    src, call,
                    "hot-path jax.jit without donate_argnums: cache/state "
                    "buffers round-trip by copy; donate or waive with the "
                    "reason nothing here is donatable"))
            elif meshed_engine and "out_shardings" not in kw:
                out.append(self.diag(
                    src, call,
                    "meshed-engine jax.jit donates but does not pin "
                    "out_shardings: GSPMD may return a re-sharded buffer "
                    "and break the compile-once trace contract"))
        return out


# ---------------------------------------------------------------------------
# R3 pspec-axis-validity
# ---------------------------------------------------------------------------

_AXIS_FIELD = re.compile(r"ax(is|es)")
_FALLBACK_AXES = frozenset({"data", "model", "pod"})


def declared_mesh_axes(project: Project) -> Tuple[Set[str], str]:
    """Mesh axis names the repo actually declares.

    Cross-checked against ``parallel/context.py`` (string defaults of
    ``ParallelCtx`` fields named ``*axis``/``*axes``) plus ``launch/mesh.py``
    (string-tuple literals — the mesh constructors' axis-name tuples).
    Falls back to the documented dp(+pod)/model axes when neither file is
    in the linted set (fixture trees).
    """
    axes: Set[str] = set()
    origin = []
    ctx = project.find_one("*parallel/context.py")
    if ctx is not None:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.ClassDef)
                    and node.name == "ParallelCtx"):
                continue
            for stmt in node.body:
                if not (isinstance(stmt, ast.AnnAssign) and stmt.value
                        and isinstance(stmt.target, ast.Name)
                        and _AXIS_FIELD.search(stmt.target.id)):
                    continue
                for sub in ast.walk(stmt.value):
                    s = A.const_str(sub)
                    if s:
                        axes.add(s)
        origin.append(ctx.rel)
    mesh = project.find_one("*launch/mesh.py")
    if mesh is not None:
        for node in ast.walk(mesh.tree):
            vals = A.str_tuple(node)
            if vals and len(vals) >= 2:
                axes.update(vals)
        origin.append(mesh.rel)
    if not axes:
        return set(_FALLBACK_AXES), "built-in fallback"
    return axes, " + ".join(origin)


class PSpecAxisRule(Rule):
    """R3: every literal ``PartitionSpec`` axis name must be a declared
    mesh axis. A typo'd axis (``P("modle")``) does not error on an
    unmeshed run — GSPMD just replicates, silently discarding the
    sharding the paper's layout depends on."""

    name = "R3-pspec-axes"
    doc = ("literal PartitionSpec axis names must be mesh axes declared "
           "in parallel/context.py / launch/mesh.py")
    exclude = TESTS

    def check_project(self, project: Project) -> Iterable[Diagnostic]:
        axes, origin = declared_mesh_axes(project)
        out: List[Diagnostic] = []
        for src in project.files:
            if not self.applies(src.rel):
                continue
            for call in A.walk_calls(src.tree):
                last = A.call_name(call).rsplit(".", 1)[-1]
                if last not in ("P", "PartitionSpec"):
                    continue
                names: List[Tuple[str, ast.AST]] = []
                for arg in call.args:
                    s = A.const_str(arg)
                    if s is not None:
                        names.append((s, arg))
                    else:
                        vals = A.str_tuple(arg)
                        if vals:
                            names.extend((v, arg) for v in vals)
                for s, node in names:
                    if s not in axes:
                        out.append(Diagnostic(
                            src.rel, node.lineno, self.name,
                            f"PartitionSpec axis {s!r} is not a declared "
                            f"mesh axis {sorted(axes)} (from {origin}); "
                            "GSPMD would silently replicate"))
        return out


# ---------------------------------------------------------------------------
# R4 fp8-scale-pairing
# ---------------------------------------------------------------------------

_FP8_NAMES = {"E4M3", "E5M2"}
_ALLOC_CALLS = {"zeros", "ones", "full", "empty", "asarray", "array",
                "zeros_like", "empty_like"}


def _is_fp8_ref(node: ast.AST) -> bool:
    if isinstance(node, ast.Name) and node.id in _FP8_NAMES:
        return True
    if isinstance(node, ast.Attribute) and "float8" in node.attr:
        return True
    s = A.const_str(node)
    return bool(s and s.startswith("float8"))


class Fp8ScalePairingRule(Rule):
    """R4: a function that *creates* fp8 values must also handle scales.

    The paper's §3.1 recipe is values+scales as a pair (1x128 tiles /
    128x128 blocks); an fp8 cast whose enclosing function never mentions a
    scale is almost always a silent-precision-loss bug (raw ``astype`` to
    E4M3 clamps at 448 with no amax rescale). Creation sites =
    ``.astype(fp8)``, ``dtype=fp8`` keywords, fp8-dtype array allocation.
    """

    name = "R4-fp8-scale"
    doc = ("functions creating fp8 values (astype/dtype=/alloc) must bind "
           "or thread a *scale* alongside")
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        parents = A.enclosing_map(src.tree)
        for call in A.walk_calls(src.tree):
            site = self._fp8_creation(call)
            if site is None:
                continue
            fns = A.enclosing_functions(call, parents)
            scope = fns[0] if fns else src.tree
            text = src.segment(scope) if fns else src.text
            if "scale" not in text.lower():
                where = (f"function `{scope.name}`"
                         if isinstance(scope, (ast.FunctionDef,
                                               ast.AsyncFunctionDef))
                         else "module scope")
                out.append(self.diag(
                    src, call,
                    f"fp8 {site} in {where} with no scale in sight: fp8 "
                    "values must travel with a matching *_scale binding "
                    "(paper §3.1 values+scales pairs)"))
        return out

    @staticmethod
    def _fp8_creation(call: ast.Call) -> Optional[str]:
        name = A.call_name(call)
        last = name.rsplit(".", 1)[-1]
        if isinstance(call.func, ast.Attribute) and \
                call.func.attr == "astype" and call.args and \
                _is_fp8_ref(call.args[0]):
            return "cast (.astype)"
        kw = A.keyword_map(call)
        if "dtype" in kw and _is_fp8_ref(kw["dtype"]):
            return "dtype= allocation"
        if last in _ALLOC_CALLS and any(
                _is_fp8_ref(a) for a in call.args):
            return f"allocation ({last})"
        return None


# ---------------------------------------------------------------------------
# R5 kernel-registry-completeness
# ---------------------------------------------------------------------------

_REQUIRED_BACKENDS = frozenset({"pallas", "interpret", "ref"})


class KernelRegistryRule(Rule):
    """R5: every registered kernel op ships all three backends, and no
    call site resurrects the pre-registry dispatch kwargs.

    Born from PR 1's near-miss: per-kernel ``interpret=True`` defaults
    would have silently run the Pallas interpreter on TPU. The registry's
    contract is pallas/interpret/ref per op and *no* caller-side backend
    choice (``use_ref=`` / literal ``interpret=True``) — backend policy
    lives in ``kernels/registry.py`` alone.
    """

    name = "R5-kernel-registry"
    doc = ("every registry.kernel() op registers pallas+interpret+ref; no "
           "use_ref=/interpret=True call sites or parameter defaults")
    exclude = TESTS

    def check_project(self, project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        for src in project.find("*kernels/*/ops.py"):
            out.extend(self._check_ops_module(src))
        return out

    def _check_ops_module(self, src: SourceFile) -> Iterable[Diagnostic]:
        # op var -> (register line, op name, backends registered)
        ops: Dict[str, Tuple[int, str, Set[str]]] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    A.call_name(node.value).rsplit(".", 1)[-1] == "kernel":
                call = node.value
                opname = (A.const_str(call.args[0])
                          if call.args else None) or "<dynamic>"
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        ops[tgt.id] = (node.lineno, opname, set())
        for fn in A.functions(src.tree):
            for dec in fn.decorator_list:
                if not (isinstance(dec, ast.Call)
                        and isinstance(dec.func, ast.Attribute)
                        and dec.func.attr == "backend"
                        and isinstance(dec.func.value, ast.Name)):
                    continue
                entry = ops.get(dec.func.value.id)
                if entry is None:
                    continue
                entry[2].update(s for s in map(A.const_str, dec.args) if s)
        for var, (line, opname, backends) in ops.items():
            missing = _REQUIRED_BACKENDS - backends
            if missing:
                yield Diagnostic(
                    src.rel, line, self.name,
                    f"kernel op {opname!r} ({var}) registers backends "
                    f"{sorted(backends)} — missing {sorted(missing)}; the "
                    "registry contract is all of pallas/interpret/ref")

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        for call in A.walk_calls(src.tree):
            kw = A.keyword_map(call)
            if "use_ref" in kw:
                out.append(self.diag(
                    src, call,
                    "legacy `use_ref=` kwarg: backend choice belongs to "
                    "kernels.registry policy, not call sites"))
            ival = kw.get("interpret")
            if isinstance(ival, ast.Constant) and ival.value is True:
                out.append(self.diag(
                    src, call,
                    "literal `interpret=True` call: would pin the Pallas "
                    "interpreter even on TPU; thread the registry's "
                    "jit-static flag instead"))
        for fn in A.functions(src.tree):
            args = fn.args
            pos = args.posonlyargs + args.args
            for arg, default in zip(pos[len(pos) - len(args.defaults):],
                                    args.defaults):
                if arg.arg == "interpret" and \
                        isinstance(default, ast.Constant) and \
                        default.value is True:
                    out.append(self.diag(
                        src, fn,
                        f"`{fn.name}` defaults interpret=True — the PR 1 "
                        "near-miss; default False and let the registry "
                        "thread the backend"))
            for arg, default in zip(args.kwonlyargs, args.kw_defaults):
                if arg.arg == "interpret" and \
                        isinstance(default, ast.Constant) and \
                        default.value is True:
                    out.append(self.diag(
                        src, fn,
                        f"`{fn.name}` defaults interpret=True — the PR 1 "
                        "near-miss; default False and let the registry "
                        "thread the backend"))
        return out


# ---------------------------------------------------------------------------
# R6 no-stray-debug
# ---------------------------------------------------------------------------

_DEBUG_CALLS = {"jax.debug.print", "jax.debug.breakpoint", "breakpoint",
                "pdb.set_trace", "ipdb.set_trace"}


class StrayDebugRule(Rule):
    """R6: no debug hooks outside tests. ``jax.debug.print`` inserts a
    host callback into the compiled program (a sync per call); a
    leftover ``breakpoint()`` hangs a headless run."""

    name = "R6-stray-debug"
    doc = "jax.debug.print/breakpoint/pdb left outside tests"
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        for call in A.walk_calls(src.tree):
            name = A.call_name(call)
            if name in _DEBUG_CALLS:
                out.append(self.diag(
                    src, call,
                    f"stray debug call `{name}` outside tests (host "
                    "callback / hang hazard in compiled programs)"))
        return out


# ---------------------------------------------------------------------------
# R7 nondeterministic-trace
# ---------------------------------------------------------------------------

_NONDET_EXACT = {"time.time", "time.perf_counter", "time.monotonic",
                 "datetime.now", "datetime.datetime.now", "datetime.utcnow"}
_NONDET_PREFIX = ("np.random.", "numpy.random.", "random.")


class NondetTraceRule(Rule):
    """R7: no wall-clock or host RNG captured inside a traced function.

    A ``time.time()``/``np.random`` value inside a jitted function or scan
    body is baked in as a constant at trace time: every retrace changes the
    program, caches never hit, and "random" is one sample replayed forever.
    JAX-side randomness must come from threaded PRNG keys.
    """

    name = "R7-nondet-trace"
    doc = ("time.*/np.random/random captured inside jitted functions or "
           "scan bodies (baked in at trace time)")
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        traced = A.jitted_functions(src.tree)
        if not traced:
            return ()
        parents = A.enclosing_map(src.tree)
        out: List[Diagnostic] = []
        for node in A.nodes_in_functions(src.tree, traced, parents):
            if not isinstance(node, ast.Call):
                continue
            name = A.call_name(node)
            if name in _NONDET_EXACT or \
                    any(name.startswith(p) for p in _NONDET_PREFIX):
                out.append(self.diag(
                    src, node,
                    f"`{name}` inside a traced scope is captured once at "
                    "trace time (nondeterministic retraces, frozen "
                    "randomness); thread a PRNG key / pass times in"))
        return out


# ---------------------------------------------------------------------------
# R8 config-completeness
# ---------------------------------------------------------------------------


class ConfigCompletenessRule(Rule):
    """R8: config modules and the model layer agree on the config schema.

    Cross-checks three ways against the dataclasses in ``configs/base.py``:
    every ``cfg.<field>`` the model layer (``models/api.py``) consumes must
    exist on ``ModelConfig``; every keyword a ``configs/*.py`` module
    passes to a config dataclass must be a declared field; and every
    non-base config module must ``register(...)`` its config so
    ``get_config`` can resolve it.
    """

    name = "R8-config-fields"
    doc = ("configs/*.py kwargs and models/api.py cfg.<attr> reads must "
           "match the dataclass fields in configs/base.py; configs must "
           "register()")
    exclude = TESTS

    def check_project(self, project: Project) -> Iterable[Diagnostic]:
        base = project.find_one("*configs/base.py")
        if base is None:
            return ()
        classes = self._dataclass_fields(base)
        out: List[Diagnostic] = []
        model_cfg = classes.get("ModelConfig")
        if model_cfg:
            fields, methods = model_cfg
            allowed = fields | methods
            api = project.find_one("*models/api.py")
            if api is not None:
                out.extend(self._check_consumers(api, allowed))
        for src in project.find("*configs/*.py"):
            if src is base:
                continue
            out.extend(self._check_config_module(src, classes))
        return out

    @staticmethod
    def _dataclass_fields(base: SourceFile
                          ) -> Dict[str, Tuple[Set[str], Set[str]]]:
        """class name -> (field names, method/property names) for every
        @dataclass in configs/base.py."""
        classes: Dict[str, Tuple[Set[str], Set[str]]] = {}
        for node in base.tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            if not any("dataclass" in A.dotted(d) for d in
                       node.decorator_list):
                continue
            fields: Set[str] = set()
            methods: Set[str] = set()
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and \
                        isinstance(stmt.target, ast.Name):
                    fields.add(stmt.target.id)
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    methods.add(stmt.name)
            classes[node.name] = (fields, methods)
        return classes

    def _check_consumers(self, api: SourceFile,
                         allowed: Set[str]) -> Iterable[Diagnostic]:
        for node in ast.walk(api.tree):
            if not isinstance(node, ast.Attribute):
                continue
            base = node.value
            is_cfg = (isinstance(base, ast.Name)
                      and base.id in ("cfg", "config")) or (
                isinstance(base, ast.Attribute) and base.attr == "cfg")
            if not is_cfg:
                continue
            if node.attr.startswith("__") or node.attr in allowed:
                continue
            yield Diagnostic(
                api.rel, node.lineno, self.name,
                f"model layer consumes `cfg.{node.attr}` but ModelConfig "
                "in configs/base.py declares no such field/method")

    def _check_config_module(self, src: SourceFile,
                             classes: Dict[str, Tuple[Set[str], Set[str]]]
                             ) -> Iterable[Diagnostic]:
        registered = False
        for call in A.walk_calls(src.tree):
            name = A.call_name(call).rsplit(".", 1)[-1]
            if name == "register":
                registered = True
            entry = classes.get(name)
            if entry is None:
                continue
            fields, _ = entry
            for k in A.keyword_map(call):
                if k not in fields:
                    yield Diagnostic(
                        src.rel, call.lineno, self.name,
                        f"{name}(... {k}=...) passes a field {name} does "
                        "not declare — models/api.py can never see it")
        if not registered and re.search(r"ModelConfig\s*\(", src.text):
            yield Diagnostic(
                src.rel, 1, self.name,
                "config module builds a ModelConfig but never register()s "
                "it — get_config cannot resolve this arch")


# ---------------------------------------------------------------------------
# R9 exception-hygiene
# ---------------------------------------------------------------------------

_BROAD_EXC = {"Exception", "BaseException"}


def _broad_exception(node: Optional[ast.AST]) -> bool:
    """True when an except clause catches Exception/BaseException (alone
    or inside a tuple)."""
    if node is None:
        return True
    if isinstance(node, ast.Tuple):
        return any(_broad_exception(e) for e in node.elts)
    return A.dotted(node).rsplit(".", 1)[-1] in _BROAD_EXC


def _swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler body does nothing with the exception: only
    pass/.../continue — no re-raise, no marking, no logging."""
    for stmt in handler.body:
        if isinstance(stmt, ast.Pass) or isinstance(stmt, ast.Continue):
            continue
        if isinstance(stmt, ast.Expr) and \
                isinstance(stmt.value, ast.Constant) and \
                stmt.value.value is Ellipsis:
            continue
        return False
    return True


class ExceptionHygieneRule(Rule):
    """R9: no swallowed faults in the fault-handling tiers.

    The serving gateway's health machinery (ISSUE 7) and the trainer's
    §6.1 failure handling both work by *observing* exceptions: a crash
    must surface as ``ReplicaCrash``, a rejected admission as
    ``AdmissionError``, so the registry/circuit-breaker/retry paths see
    it. A bare ``except:`` (which also eats ``KeyboardInterrupt``) or an
    ``except Exception: pass`` anywhere in ``serve/**`` or ``train/**``
    silently converts a detectable fault into a hang or wrong answer —
    exactly the failure mode the heartbeat escalation exists to catch.
    Broad catches that *handle* (re-raise, mark state, log) are fine;
    broad catches that swallow are not.
    """

    name = "R9-exception-hygiene"
    doc = ("no bare `except:` or swallowed `except Exception: pass` in "
           "src/repro/serve/** and src/repro/train/** (swallowed faults "
           "defeat the health machinery)")
    include = ("*serve/*.py", "*train/*.py")
    exclude = TESTS

    def check_file(self, src: SourceFile,
                   project: Project) -> Iterable[Diagnostic]:
        out: List[Diagnostic] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                out.append(Diagnostic(
                    src.rel, node.lineno, self.name,
                    "bare `except:` catches everything (including "
                    "KeyboardInterrupt) and hides faults from the health "
                    "machinery; name the exceptions you mean"))
            elif _broad_exception(node.type) and _swallows(node):
                caught = A.dotted(node.type) if not isinstance(
                    node.type, ast.Tuple) else "Exception"
                out.append(Diagnostic(
                    src.rel, node.lineno, self.name,
                    f"`except {caught}: pass` swallows the fault the "
                    "registry/circuit-breaker/retry paths need to see; "
                    "handle it, re-raise, or catch the specific type"))
        return out


ALL_RULES: Tuple[Rule, ...] = (
    HostSyncRule(),
    JitContractRule(),
    PSpecAxisRule(),
    Fp8ScalePairingRule(),
    KernelRegistryRule(),
    StrayDebugRule(),
    NondetTraceRule(),
    ConfigCompletenessRule(),
    ExceptionHygieneRule(),
)
