"""LC*: dispatch-ladder coverage — warmed and accounted, or not shipped.

The serving invariant since PR 1 is ZERO live-traffic compiles: every
fused program (``self.*_fn`` jit handles) and every static-shape bucket
ladder (``self.*buckets``) a dispatch site uses must be compiled by
``warmup()`` — one missed bucket is a hidden multi-second XLA compile on
the first live request that needs it (exactly the class of bug PR 5 fixed
for mesh-sharded deployments). ``compile_counts()`` is the observability
half: a program it does not report is invisible to the
``recompiles_since_warmup()`` zero-recompile gate.

- LC001: a ``*_fn`` program handle dispatched outside ``warmup()`` but
  never exercised by it (warmup's own helper methods count — the closure
  over ``self.<method>()`` calls is followed).
- LC002: a dispatched ``*_fn`` handle missing from
  ``compile_counts()``/``compile_count()`` (a helper method warmup calls
  is still a dispatch site here: serving/decode_programs.py warms by the
  live conventions).
- LC003: a ``*buckets`` ladder read at a dispatch site but never walked
  by ``warmup()``.

Classes without a ``warmup`` method are out of scope (nothing promises
pre-compilation there).
"""

from __future__ import annotations

import ast

from seldon_core_tpu.analysis.core import ParsedFile, Project
from seldon_core_tpu.analysis.model import Finding


def _is_self_attr(node: ast.AST) -> str | None:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _attrs_used(fn: ast.AST) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(fn):
        attr = _is_self_attr(node)
        if attr is not None:
            out.add(attr)
    return out


def _self_calls(fn: ast.AST) -> set[str]:
    out: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            attr = _is_self_attr(node.func)
            if attr is not None:
                out.add(attr)
    return out


class LadderCoveragePass:
    name = "ladder"
    rules = {
        "LC001": "fused program handle dispatched but never compiled by warmup()",
        "LC002": "fused program handle missing from compile_counts()",
        "LC003": "bucket ladder used at a dispatch site but not walked by warmup()",
    }

    def run(self, project: Project) -> list[Finding]:
        findings: list[Finding] = []
        for pf in project.files:
            for node in ast.walk(pf.tree):
                if isinstance(node, ast.ClassDef):
                    self._check_class(pf, node, findings)
        return findings

    def _check_class(
        self, pf: ParsedFile, cls: ast.ClassDef, findings: list[Finding]
    ) -> None:
        methods = {
            n.name: n
            for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        warmup = methods.get("warmup")
        if warmup is None:
            return
        counts = methods.get("compile_counts") or methods.get("compile_count")

        # warmup's closure: attrs it (or the self-methods it calls,
        # transitively) touches
        warmed: set[str] = set()
        seen: set[str] = set()
        frontier = ["warmup"]
        while frontier:
            name = frontier.pop()
            if name in seen or name not in methods:
                continue
            seen.add(name)
            warmed |= _attrs_used(methods[name])
            frontier.extend(_self_calls(methods[name]))
        counted = _attrs_used(counts) if counts is not None else None

        # dispatch sites: first use of each handle outside warmup itself
        # (a round-kind method warmup calls to compile by the live
        # convention — serving/decode_programs.py — is still a dispatch
        # site whose handle must be counted), of each ladder outside
        # warmup's closure (the compile site, not a dispatch)
        handles: dict[str, ast.AST] = {}
        ladders: dict[str, ast.AST] = {}
        for mname, m in methods.items():
            if mname == "warmup":
                continue
            for node in ast.walk(m):
                if isinstance(node, ast.Call):
                    attr = _is_self_attr(node.func)
                    if attr and attr.endswith("_fn"):
                        handles.setdefault(attr, node)
                attr = _is_self_attr(node)
                if (
                    mname not in seen
                    and attr
                    and (attr == "buckets" or attr.endswith("_buckets"))
                    and isinstance(node.ctx, ast.Load)
                ):
                    ladders.setdefault(attr, node)

        for attr, site in sorted(handles.items()):
            if attr not in warmed:
                findings.append(
                    Finding(
                        rule="LC001",
                        path=pf.path,
                        line=site.lineno,
                        col=site.col_offset,
                        message=(
                            f"`self.{attr}` is dispatched but `{cls.name}"
                            ".warmup()` never compiles it — the first live "
                            "request pays the XLA compile"
                        ),
                        hint="exercise every bucket of the program in warmup()",
                        symbol=f"{cls.name}.{attr}",
                    )
                )
            if counted is not None and attr not in counted:
                findings.append(
                    Finding(
                        rule="LC002",
                        path=pf.path,
                        line=site.lineno,
                        col=site.col_offset,
                        message=(
                            f"`self.{attr}` is dispatched but not reported "
                            f"by `{cls.name}.compile_counts()` — recompiles "
                            "of it are invisible to the zero-recompile gate"
                        ),
                        hint="add the program's _cache_size() to compile_counts()",
                        symbol=f"{cls.name}.{attr}",
                    )
                )
        for attr, site in sorted(ladders.items()):
            if attr not in warmed:
                findings.append(
                    Finding(
                        rule="LC003",
                        path=pf.path,
                        line=site.lineno,
                        col=site.col_offset,
                        message=(
                            f"ladder `self.{attr}` feeds a dispatch site but "
                            f"`{cls.name}.warmup()` never walks it — "
                            "unwarmed buckets compile on the live path"
                        ),
                        hint="iterate the full ladder in warmup()",
                        symbol=f"{cls.name}.{attr}",
                    )
                )
