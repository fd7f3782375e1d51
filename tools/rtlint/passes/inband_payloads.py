"""inband-payloads pass: hot-path RPC/channel sends must not carry raw
packed payloads in-band.

Ported from tools/check_inband_payloads.py (now a shim).  The zero-copy
data plane (utils/rpc.py multi-segment frames) only stays zero-copy if
bulk payloads reach the RPC layer as out-of-band-capable values:
ndarrays (pickle-5 splits them automatically) or packed frames wrapped
in ``serialization.Frame`` / ``serialization.maybe_frame``.  A call site
that passes ``serialization.pack(...)`` / ``dumps(...)`` /
``pack_parts(...)`` output (or ``.tobytes()`` / ``bytes(view)``)
straight into an RPC send re-introduces the in-band memcpy — and
nothing would fail, it would just be slow.

Flags:

1. a raw-serializer call appearing DIRECTLY as an argument of an RPC
   send (``.call`` / ``.call_async`` / ``.call_oneway`` / ``.push`` /
   ``.push_encoded`` / ``reply``; plus channel ``.write`` in the
   compiled exec-loop modules);
2. the same through a local alias (fixpoint propagation);
3. the same in a ``return`` of an RPC REPLY producer (``rpc_*`` /
   ``handle_request_direct``): its return value IS the response payload.

Wrapping in ``serialization.Frame(...)`` / ``maybe_frame(...)`` cleans a
value.  Only the modules in HOT_PATHS are checked.  A line may opt out
with ``# inband: ok`` (e.g. the WAL append, where durability needs one
contiguous record) or ``# rtlint: ignore[inband-payloads] <reason>``.
"""

from __future__ import annotations

import ast
import os
from typing import List, Optional, Set, Tuple

from tools.rtlint.engine import FileContext, LintPass

HOT_PATHS = (
    os.path.join("ray_tpu", "core", "worker.py"),
    os.path.join("ray_tpu", "core", "node_agent.py"),
    os.path.join("ray_tpu", "serve", "proxy.py"),
    os.path.join("ray_tpu", "serve", "replica.py"),
    os.path.join("ray_tpu", "serve", "router.py"),
    # serve control loop: the controller's reconcile tick issues RPC
    # sends (status publish, drain kills); payloads must stay tiny
    # control records — the ~1 KiB autoscale_status JSON is opted out
    # per line, anything bulkier must ride a Frame
    os.path.join("ray_tpu", "serve", "controller.py"),
    # collective transport: ring chunk deliveries must pass ndarrays /
    # Frame-wrapped values so they ride as out-of-band segments; only
    # the KV fallback (which stores contiguous blobs by design) and the
    # ~100 B rendezvous records may pack in-band (opted out per line)
    os.path.join("ray_tpu", "collective", "p2p.py"),
    os.path.join("ray_tpu", "collective", "collective.py"),
    # bucketed grad sync: multi-MB gradient buckets go through
    # p2p.send_async as raw ndarrays (out-of-band segments); only the
    # coalesced KV-fallback exchange may pack — and _exchange is a KV
    # publish, not an RPC send, so it stays clean by construction
    os.path.join("ray_tpu", "collective", "bucketed.py"),
    # compiled-graph / compiled-pipeline exec loops: microbatch
    # activations move via channel writes — see CHANNEL_SEND_PATHS
    os.path.join("ray_tpu", "dag.py"),
    os.path.join("ray_tpu", "parallel", "pipeline.py"),
    # paged KV engine: what it holds is whole KV pages (multi-MB
    # ndarrays); any send added here must pass the arrays themselves (or
    # Frame-wrapped packs), never pack(...) output in-band
    os.path.join("ray_tpu", "serve", "llm.py"),
)

RPC_SEND_METHODS = {"call", "call_async", "call_oneway", "push",
                    "push_encoded", "reply"}
# In the compiled exec-loop modules a channel ``.write(pack(...))`` is
# the same in-band join-copy an RPC send would be: activations ≥32 KiB
# must ride ``write_value``/``write_views`` (scatter-gather straight
# into the shm slot; Frame-wrapped multiseg segments on the RpcChannel
# tier). Only the tiny _STOP sentinel goes through raw ``.write``.
CHANNEL_SEND_METHODS = {"write"}
CHANNEL_SEND_PATHS = (
    os.path.join("ray_tpu", "dag.py"),
    os.path.join("ray_tpu", "parallel", "pipeline.py"),
)


def send_methods_for(filename: str):
    """The send-method set a file is checked against: RPC sends
    everywhere, plus channel writes in the exec-loop modules."""
    if filename.endswith(CHANNEL_SEND_PATHS):
        return RPC_SEND_METHODS | CHANNEL_SEND_METHODS
    return RPC_SEND_METHODS


RAW_SERIALIZERS = {"pack", "dumps", "pack_parts"}
WRAPPERS = {"Frame", "maybe_frame"}
# reply producers: the return value travels as the RPC response payload
DIRECT_REPLY_FNS = {"handle_request_direct"}
OPT_OUT_MARK = "# inband: ok"


def _call_attr(node: ast.AST) -> str:
    """Method name of a Call through an attribute, else ''. """
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        return node.func.attr
    return ""


def _is_raw_serializer_call(node: ast.AST) -> bool:
    """serialization.pack(...) / dumps(...) / pack_parts(...) /
    x.tobytes() / bytes(...)."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Attribute):
        if fn.attr in RAW_SERIALIZERS or fn.attr == "tobytes":
            return True
    if isinstance(fn, ast.Name) and fn.id == "bytes" and node.args:
        return True
    return False


def _is_wrapper_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _call_attr(node) in WRAPPERS or (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id in WRAPPERS
    )


def _raw_aliases(fn: ast.AST) -> Set[str]:
    """Names assigned (possibly transitively) from a raw serializer call
    within one function, to a fixpoint. A name reassigned from a wrapper
    is NOT cleaned retroactively — one dirty binding taints the name for
    the whole function (static over-approximation, opt out per line)."""
    aliases: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            value = node.value
            dirty = _is_raw_serializer_call(value) or (
                isinstance(value, ast.Name) and value.id in aliases
            )
            if not dirty:
                continue
            for t in node.targets:
                for sub in ast.walk(t):
                    if (
                        isinstance(sub, ast.Name)
                        and isinstance(sub.ctx, ast.Store)
                        and sub.id not in aliases
                    ):
                        aliases.add(sub.id)
                        changed = True
    return aliases


def _payload_args(call: ast.Call):
    for a in call.args:
        yield a
    for kw in call.keywords:
        yield kw.value


def _dirty_payloads(call: ast.Call, aliases: Set[str]):
    """Raw-serializer expressions reaching an RPC send call's arguments,
    at any nesting depth — but never looking INSIDE a wrapper call."""
    yield from _dirty_payloads_expr(list(_payload_args(call)), aliases)


def _dirty_payloads_expr(root, aliases: Set[str]):
    """Raw-serializer expressions anywhere in an expression (or list of
    expressions), never looking INSIDE a wrapper call."""
    stack = list(root) if isinstance(root, list) else [root]
    while stack:
        node = stack.pop()
        if _is_wrapper_call(node):
            continue  # wrapped payloads are clean, whatever is inside
        if _is_raw_serializer_call(node):
            yield node
            continue
        if isinstance(node, ast.Name) and node.id in aliases:
            yield node
            continue
        for child in ast.iter_child_nodes(node):
            stack.append(child)


def scan(
    tree: ast.Module,
    lines: List[str],
    filename: str,
    send_methods: Optional[Set[str]] = None,
) -> List[Tuple[int, str]]:
    """Core rule: (lineno, message) pairs, ``# inband: ok`` applied."""
    if send_methods is None:
        send_methods = send_methods_for(filename)
    violations: List[Tuple[int, str]] = []

    def opted_out(lineno: int) -> bool:
        return 0 < lineno <= len(lines) and OPT_OUT_MARK in lines[lineno - 1]

    functions = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for fn in functions:
        aliases = _raw_aliases(fn)
        for node in ast.walk(fn):
            if _call_attr(node) not in send_methods:
                continue
            for dirty in _dirty_payloads(node, aliases):
                if opted_out(node.lineno) or opted_out(dirty.lineno):
                    continue
                what = (
                    f"alias {dirty.id!r}" if isinstance(dirty, ast.Name)
                    else "serializer output"
                )
                violations.append((
                    node.lineno,
                    f"in {fn.name}(): raw in-band payload ({what}) passed "
                    f"to .{_call_attr(node)}() — wrap in "
                    f"serialization.Frame/maybe_frame or pass the value "
                    f"itself",
                ))
        if not (fn.name.startswith("rpc_") or fn.name in DIRECT_REPLY_FNS):
            continue
        # reply producers: returns are response payloads (rule 3). Only
        # THIS function's returns — nested defs (closures, streaming
        # generators) reply through other channels.
        nested = {
            inner
            for outer in ast.walk(fn)
            if isinstance(outer, (ast.FunctionDef, ast.AsyncFunctionDef))
            and outer is not fn
            for inner in ast.walk(outer)
        }
        for node in ast.walk(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            if node in nested:
                continue
            for dirty in _dirty_payloads_expr(node.value, aliases):
                if opted_out(node.lineno) or opted_out(dirty.lineno):
                    continue
                what = (
                    f"alias {dirty.id!r}" if isinstance(dirty, ast.Name)
                    else "serializer output"
                )
                violations.append((
                    node.lineno,
                    f"in {fn.name}(): raw in-band payload ({what}) "
                    f"returned as an RPC reply — wrap in "
                    f"serialization.Frame/maybe_frame",
                ))
    return violations


class InbandPayloadsPass(LintPass):
    id = "inband-payloads"
    title = "in-band payloads"
    doc = ("hot-path RPC/channel sends must not carry raw packed "
           "payloads in-band (wrap in serialization.Frame/maybe_frame)")

    def select(self, relpath: str) -> bool:
        return relpath.endswith(HOT_PATHS)

    def run(self, ctx: FileContext) -> List[Tuple[int, str]]:
        return scan(ctx.tree, ctx.lines, ctx.relpath)


PASS = InbandPayloadsPass()


# --- legacy API (tools/check_inband_payloads.py shims to these) ------------

def check_source(src: str, filename: str = "<source>",
                 send_methods=None) -> List[str]:
    tree = ast.parse(src, filename=filename)
    return [
        f"{filename}:{lineno}: {msg}"
        for lineno, msg in scan(
            tree, src.splitlines(), filename, send_methods
        )
    ]


def check_file(path: str) -> List[str]:
    with open(path) as f:
        return check_source(f.read(), filename=path)


def main(argv: List[str]) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    paths = argv[1:] or [os.path.join(repo, p) for p in HOT_PATHS]
    violations: List[str] = []
    for p in paths:
        violations.extend(check_file(p))
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} in-band payload violation(s)")
        return 1
    print(f"{len(paths)} hot-path file(s): no in-band bulk payloads")
    return 0
