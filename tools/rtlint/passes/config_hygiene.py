"""config-hygiene pass: every RT_* env read goes through utils/config,
and every registered flag is documented in README and read somewhere.

``ray_tpu/utils/config.py`` is the single place RT_* environment
variables become configuration: ``config.define(name, default)``
registers the flag, infers the parser, applies the ``RT_<NAME>``
override, and ships head-side values to nodes via ``snapshot()``.  A
raw ``os.environ.get("RT_X")`` elsewhere silently forks that contract:
the value never rides the snapshot, never shows up in ``rt top``'s
config dump, and parses differently per call site.

Per-file rule (cached): any read of an ``RT_*`` environment variable —
``os.environ.get/[]``, ``os.getenv``, ``"RT_X" in os.environ``, with
the key a string literal or a module-level constant — outside
``utils/config.py`` is a violation.  Writes (``os.environ[k] = v``) are
the runtime-env apply path and are not flagged.

Project rules (uncached, anchored at the ``define`` line in
utils/config.py): every registered flag's ``RT_<NAME>`` must appear in
README.md, and every registered flag must be read by some file under
``ray_tpu/`` other than utils/config.py (``config.<name>``, or its name
as a string handed to ``config.get`` / ``getattr``): a flag nothing
reads is a documented promise nothing keeps.  Suppress any with the
usual ignore comment naming
``config-hygiene`` plus a reason (e.g. the worker/node/head boot
protocol, which must read ``RT_CONFIG_SNAPSHOT`` before any config
exists).
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set, Tuple

from tools.rtlint.engine import (
    FileContext,
    Finding,
    LintPass,
    _iter_py_files,
    parse_suppressions,
)

CONFIG_RELPATH = os.path.join("ray_tpu", "utils", "config.py")
ENV_PREFIX = "RT_"


def _env_key(node: ast.AST, consts) -> Optional[str]:
    """The RT_* key named by an expression (literal or module constant),
    else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        key = node.value
    elif isinstance(node, ast.Name) and isinstance(
        consts.get(node.id), str
    ):
        key = consts[node.id]
    else:
        return None
    return key if key.startswith(ENV_PREFIX) else None


def _is_os_environ(node: ast.AST) -> bool:
    """``os.environ`` or a bare ``environ`` name."""
    if (
        isinstance(node, ast.Attribute)
        and node.attr == "environ"
        and isinstance(node.value, ast.Name)
        and node.value.id == "os"
    ):
        return True
    return isinstance(node, ast.Name) and node.id == "environ"


def scan(tree: ast.Module, consts) -> List[Tuple[int, str]]:
    out: List[Tuple[int, str]] = []

    def flag(node: ast.AST, key: str) -> None:
        out.append((
            node.lineno,
            f"raw read of {key} bypasses utils/config — register the "
            f"flag with config.define() and read config.<name>",
        ))

    for node in ast.walk(tree):
        # os.environ.get("RT_X") / os.getenv("RT_X")
        if isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            f = node.func
            key = _env_key(node.args[0], consts) if node.args else None
            if key is None:
                continue
            if f.attr in ("get", "pop") and _is_os_environ(f.value):
                flag(node, key)
            elif (
                f.attr == "getenv"
                and isinstance(f.value, ast.Name)
                and f.value.id == "os"
            ):
                flag(node, key)
        elif isinstance(node, ast.Call) and isinstance(
            node.func, ast.Name
        ) and node.func.id == "getenv" and node.args:
            key = _env_key(node.args[0], consts)
            if key:
                flag(node, key)
        # os.environ["RT_X"] (reads only)
        elif isinstance(node, ast.Subscript) and isinstance(
            node.ctx, ast.Load
        ) and _is_os_environ(node.value):
            key = _env_key(node.slice, consts)
            if key:
                flag(node, key)
        # "RT_X" in os.environ
        elif isinstance(node, ast.Compare) and any(
            isinstance(op, (ast.In, ast.NotIn)) for op in node.ops
        ):
            if any(_is_os_environ(c) for c in node.comparators):
                key = _env_key(node.left, consts)
                if key:
                    flag(node, key)
    return out


def registered_flags(config_src: str) -> List[Tuple[int, str]]:
    """(lineno, flag_name) for every ``*.define("name", ...)`` call in
    utils/config.py."""
    tree = ast.parse(config_src)
    out: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "define"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            out.append((node.lineno, node.args[0].value))
    return out


def names_used(root: str) -> Set[str]:
    """Every attribute name and every whole string constant of the files
    under ``ray_tpu/`` but utils/config.py: what a flag's name must be
    among to count as read."""
    used: Set[str] = set()
    for rel in _iter_py_files(root, ["ray_tpu"]):
        if rel == CONFIG_RELPATH:
            continue
        try:
            with open(os.path.join(root, rel)) as f:
                tree = ast.parse(f.read())
        except (OSError, SyntaxError):
            continue  # the per-file passes report what does not parse
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute):
                used.add(node.attr)
            elif isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                used.add(node.value)
    return used


class ConfigHygienePass(LintPass):
    id = "config-hygiene"
    title = "config hygiene"
    doc = ("RT_* env reads must go through utils/config registration; "
           "every registered flag must be documented in README and read "
           "under ray_tpu/")

    def select(self, relpath: str) -> bool:
        parts = relpath.split(os.sep)
        return parts[0] == "ray_tpu" and relpath != CONFIG_RELPATH

    def run(self, ctx: FileContext) -> List[Tuple[int, str]]:
        return scan(ctx.tree, ctx.module_constants)

    def project_check(self, root: str) -> List[Finding]:
        """Registered-flag ↔ README and ↔ reader cross-checks.  Runs
        uncached; honors ``# rtlint: ignore[config-hygiene]`` on the
        define line."""
        config_path = os.path.join(root, CONFIG_RELPATH)
        readme_path = os.path.join(root, "README.md")
        try:
            with open(config_path) as f:
                config_src = f.read()
        except OSError:
            return []
        try:
            with open(readme_path) as f:
                readme = f.read()
        except OSError:
            readme = ""
        sups = parse_suppressions(config_src.splitlines())
        used = names_used(root)
        out: List[Finding] = []
        for lineno, name in registered_flags(config_src):
            env = ENV_PREFIX + name.upper()
            messages = []
            if env not in readme:
                messages.append(
                    f"flag {name!r} ({env}) is not documented in "
                    f"README.md — add it to the configuration table"
                )
            if name not in used:
                messages.append(
                    f"flag {name!r} ({env}) is read by no file under "
                    f"ray_tpu/ — delete it, or the code that was to read it"
                )
            for message in messages:
                finding = Finding(
                    file=CONFIG_RELPATH,
                    line=lineno,
                    pass_id=self.id,
                    message=message,
                )
                sup = sups.get(lineno)
                if sup and self.id in sup.pass_ids and sup.reason:
                    finding.suppressed = True
                    finding.reason = sup.reason
                out.append(finding)
        return out


PASS = ConfigHygienePass()
