"""Pass 1 — AST kernel-contract linter (rules KC101–KC107).

Enforces the dispatch-plane conventions the engines already follow, so a
new engine (or a refactor of an old one) cannot quietly drop them:

  KC101  ``interpret=`` literal at a call site.  The interpret flag must
         thread through ``ops._mode`` / ``kernel_mode`` so one env
         accessor governs every launch; a literal pins a kernel to one
         mode and splits the jit cache.
  KC102  raw Pallas kernel called outside its defining module by a
         function that never touches ``KERNEL_CALLS``.  Untallied
         dispatches make the tally lie — PR 3's silent-bypass bug.
  KC103  ``pallas_call`` inside a state-carried wrapper (function name
         contains ``state``) without ``input_output_aliases``.  An
         unaliased carry reallocates the machine bricks every chunk.
  KC104  ``pl.BlockSpec`` block shape written as an all-literal tuple.
         Brick shapes must come from the shared layout contract
         (``LANES``/``SUBLANES``/``lcap``/``block_e`` names) so kernel
         and host packers cannot drift apart.
  KC105  degradation arm around kernel dispatch that catches more than
         the dispatcher's own decline (``KernelDeclined``) — a bare
         ``NotImplementedError`` is also what a Pallas lowering raises,
         and ``ImportError`` hides a broken install, so either would
         reroute a kernel fault onto the XLA scan — or that never calls
         ``record_fallback``.  Silent downgrades are invisible to
         telemetry and benchmarks.
  KC106  direct ``os.environ`` read of the interpret-mode variables
         outside ``kernels/tally.py``.  One accessor
         (``interpret_requested``) owns the env aliases.
  KC107  shadow dispatch tally outside the accessor module: a direct
         ``REGISTRY.counter("kernel_calls", ...)`` write, or a
         ``KERNEL_CALLS["fallback:..."]`` write instead of
         ``record_fallback``.  The ``kernel_calls`` registry family is
         owned by ``kernels/tally.py`` — a second writer lets the audit
         artifact, the health snapshot, and exported metrics drift.

``lint_source`` lints one snippet (used by the analyzer's own tests);
``lint_tree`` walks a source root and applies ``# audit-ok:`` markers.
"""

from __future__ import annotations

import ast
import pathlib

from .findings import Finding, split_suppressed

# raw pallas_call wrappers; calls anywhere outside their defining modules
# must be fronted by a KERNEL_CALLS tally (KC102)
KERNEL_WRAPPERS = frozenset({
    "a1_count_kernel", "a1_count_state_kernel", "a1_mapconcat_kernel",
    "a2_count_kernel", "a2_count_state_kernel", "a2_mapconcat_kernel",
})
KERNEL_DEF_MODULES = ("kernels/a1_count.py", "kernels/a2_count.py")

INTERPRET_ENV_VARS = ("REPRO_KERNEL_INTERPRET", "REPRO_INTERPRET_KERNELS")
ENV_ACCESSOR_MODULE = "kernels/tally.py"
# the registry family kernels/tally.py owns; KC107 rejects other writers
TALLY_FAMILY = "kernel_calls"


def _call_name(node: ast.Call) -> str:
    """Trailing name of the called object (``kops.a1_count`` -> a1_count)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _contains_call(tree, name: str) -> bool:
    return any(isinstance(n, ast.Call) and _call_name(n) == name
               for n in ast.walk(tree))


def _touches_kernel_calls(fn: ast.AST) -> bool:
    return any(isinstance(n, ast.Name) and n.id == "KERNEL_CALLS"
               or isinstance(n, ast.Attribute) and n.attr == "KERNEL_CALLS"
               for n in ast.walk(fn))


def _handler_names(handler: ast.ExceptHandler) -> set[str]:
    t = handler.type
    elts = t.elts if isinstance(t, ast.Tuple) else [t] if t else []
    out = set()
    for e in elts:
        if isinstance(e, ast.Name):
            out.add(e.id)
        elif isinstance(e, ast.Attribute):
            out.add(e.attr)
    return out


def _uses_kernel_plane(body) -> bool:
    """Does this ``try`` body import or call into ``repro.kernels``?"""
    for stmt in body:
        for n in ast.walk(stmt):
            if isinstance(n, ast.ImportFrom) and n.module and \
                    n.module.startswith("repro.kernels"):
                return True
            if isinstance(n, ast.Name) and n.id == "kops":
                return True
            if isinstance(n, ast.Call) and \
                    _call_name(n) == "kernel_mode":
                return True
    return False


def _const_str(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _is_os_environ(node) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id == "os")


def lint_source(source: str, path: str) -> list[Finding]:
    """Lint one module's source. Returns raw findings (no suppression —
    ``lint_tree`` applies the ``# audit-ok`` markers)."""
    tree = ast.parse(source, filename=path)
    findings: list[Finding] = []
    posix = pathlib.PurePosixPath(path).as_posix()
    in_kernel_def = posix.endswith(KERNEL_DEF_MODULES)
    in_accessor = posix.endswith(ENV_ACCESSOR_MODULE)

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    for node in ast.walk(tree):
        # KC101 — interpret literal at a call site
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg == "interpret" and \
                        isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, bool):
                    findings.append(Finding(
                        "KC101", path, kw.value.lineno,
                        f"interpret={kw.value.value} literal — thread the "
                        "flag through ops._mode()/kernel_mode() instead"))

        # KC104 — all-literal BlockSpec block shape
        if isinstance(node, ast.Call) and \
                _call_name(node) == "BlockSpec" and node.args:
            shape = node.args[0]
            if isinstance(shape, ast.Tuple) and shape.elts and all(
                    isinstance(e, ast.Constant) and
                    isinstance(e.value, int) for e in shape.elts):
                vals = [e.value for e in shape.elts]
                if max(vals) > 1:  # (1, 1)-style degenerate specs are fine
                    findings.append(Finding(
                        "KC104", path, shape.lineno,
                        f"literal block shape {tuple(vals)} — derive brick "
                        "shapes from the layout contract "
                        "(LANES/SUBLANES/lcap/block_e)"))

        # KC105 — kernel→XLA degradation that is too broad or unrecorded
        if isinstance(node, ast.Try) and _uses_kernel_plane(node.body):
            for h in node.handlers:
                names = _handler_names(h)
                broad = names & {"NotImplementedError", "ImportError"}
                if broad:
                    findings.append(Finding(
                        "KC105", path, h.lineno,
                        f"degradation arm catches {sorted(broad)} — a "
                        "kernel lowering/compile fault would reroute "
                        "silently; catch KernelDeclined only"))
                    continue
                if "KernelDeclined" not in names:
                    continue
                body = ast.Module(body=h.body, type_ignores=[])
                if not _contains_call(body, "record_fallback"):
                    findings.append(Finding(
                        "KC105", path, h.lineno,
                        "kernel→XLA degradation arm without "
                        "record_fallback() — downgrade is invisible "
                        "to the dispatch tally"))

        # KC106 — direct env read of the interpret aliases
        if not in_accessor:
            key = None
            if isinstance(node, ast.Subscript) and \
                    _is_os_environ(node.value):
                key = _const_str(node.slice)
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "get" and \
                    _is_os_environ(node.func.value) and node.args:
                key = _const_str(node.args[0])
            if key in INTERPRET_ENV_VARS:
                findings.append(Finding(
                    "KC106", path, node.lineno,
                    f"direct os.environ read of {key} — use "
                    "kernels.tally.interpret_requested()"))

        # KC107 — shadow dispatch tally outside the accessor module
        if not in_accessor:
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("counter", "gauge", "histogram") \
                    and node.args and _const_str(node.args[0]) \
                    == TALLY_FAMILY:
                findings.append(Finding(
                    "KC107", path, node.lineno,
                    f"direct registry write to the {TALLY_FAMILY!r} "
                    "family — the dispatch tally is owned by "
                    "kernels.tally (KERNEL_CALLS / record_fallback)"))
            if isinstance(node, ast.Subscript) and (
                    isinstance(node.value, ast.Name)
                    and node.value.id == "KERNEL_CALLS"
                    or isinstance(node.value, ast.Attribute)
                    and node.value.attr == "KERNEL_CALLS"):
                key = _const_str(node.slice)
                if key is not None and key.startswith("fallback:"):
                    findings.append(Finding(
                        "KC107", path, node.lineno,
                        f"KERNEL_CALLS[{key!r}] written directly — "
                        "record a degradation through "
                        "kernels.tally.record_fallback(site)"))

    for fn in funcs:
        # KC102 — untallied raw kernel dispatch outside defining module
        if not in_kernel_def:
            calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)
                     and _call_name(n) in KERNEL_WRAPPERS]
            if calls and not _touches_kernel_calls(fn):
                findings.append(Finding(
                    "KC102", path, calls[0].lineno,
                    f"{_call_name(calls[0])}() dispatched without a "
                    "KERNEL_CALLS tally in the same function"))

        # KC103 — state-carried pallas_call without donation aliases
        if "state" in fn.name:
            for n in ast.walk(fn):
                if isinstance(n, ast.Call) and \
                        _call_name(n) == "pallas_call" and not any(
                            kw.arg == "input_output_aliases"
                            for kw in n.keywords):
                    findings.append(Finding(
                        "KC103", path, n.lineno,
                        f"state-carried pallas_call in {fn.name}() "
                        "without input_output_aliases — the machine "
                        "bricks reallocate every chunk"))

    return findings


def lint_tree(root) -> tuple[list[Finding], list[Finding], dict]:
    """Lint every ``*.py`` under ``root``.

    Returns (active findings, suppressed findings, summary dict); paths
    in findings are relative to ``root``'s parent so reports read like
    ``repro/core/...``.
    """
    root = pathlib.Path(root)
    findings: list[Finding] = []
    sources: dict[str, list[str]] = {}
    n_files = 0
    for py in sorted(root.rglob("*.py")):
        rel = py.relative_to(root.parent).as_posix()
        text = py.read_text()
        sources[rel] = text.splitlines()
        findings.extend(lint_source(text, rel))
        n_files += 1
    active, waived = split_suppressed(findings, sources)
    return active, waived, {"files_linted": n_files}
