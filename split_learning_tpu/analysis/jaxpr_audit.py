"""Jaxpr hot-path auditor.

Two complementary passes over the compiled training path:

* **Jaxpr pass** (JX002-JX004): build a tiny
  :class:`~split_learning_tpu.runtime.client.ShardRunner` (KWT on
  synthetic MFCC shapes — the cheapest registered model), trace its
  jitted train ops to jaxprs with ``jax.make_jaxpr`` over
  ``jax.eval_shape``-derived parameter shapes (zero FLOPs, no
  compile), and flag:

  - JX002 — fp32 upcasts on the bf16 wire path: the hot loop's
    wire-bound outputs (stage boundary activations / input gradients,
    after the device-side wire cast) carry a float dtype wider than
    ``transport.wire-dtype``, so every tick fetches double the bytes
    the wire will ship;
  - JX003 — host round-trips compiled into the step (callback /
    infeed / outfeed primitives) and float64 avals (x64 drift blows
    the recompile cache and doubles buffer sizes);
  - JX004 — nondeterministic trace: tracing the same op twice yields
    different jaxprs (a ``time``/``random`` call leaked into trace
    time — every retrace recompiles).

* **AST pass** (JX001, JX005, JX006): walk the tick-loop sources and
  flag

  - JX001 — implicit device→host syncs inside a hot loop:
    ``float()``/``int()``/``bool()``/``.item()``/``np.asarray``/
    ``jax.device_get``/``block_until_ready`` applied to a jitted op's
    result (or any ``jnp.*`` expression).  Escape hatch for audited
    syncs: trailing ``# slcheck: allow-sync``;
  - JX005 — donated-then-reused buffers: a call to a train step whose
    maker donates argument positions must rebind those arguments from
    the result in the same statement (the convention every call site
    follows — a later read of a donated buffer is undefined);
  - JX006 — ``jax.jit`` invoked inside a loop body (a fresh jit wrapper
    per iteration defeats the compile cache);
  - JX007 — non-donated round-boundary update buffers: in the
    aggregation plane (``runtime/aggregate.py``) every ``jax.jit``
    whose function takes a running-accumulator parameter (``acc`` /
    ``stat_acc`` — the module's naming convention) must donate those
    positions, or each fold/update allocates a fresh full-stage buffer
    instead of updating in place.  The jaxpr pass additionally traces
    the fused sharded stage update (``MeshFoldBackend.stage_update``)
    and flags host round-trips compiled into it (JX003) and
    fp32-upcast-on-bf16-wire outputs (a leaf declared bf16 must come
    back bf16 — JX002) — the buffer-donation audit the sharded
    weight-update plane is gated by.
"""

from __future__ import annotations

import ast
import pathlib
import re

from split_learning_tpu.analysis.findings import Finding

#: hot functions per source file; "loops" audits loop bodies only,
#: "all" audits the whole body (helpers invoked per tick)
HOT_FUNCTIONS = {
    "split_learning_tpu/runtime/client.py": {
        "_train_whole": "loops", "_train_first": "loops",
        "_train_middle": "loops", "_train_last": "loops",
        "_sda_step": "all",
    },
    "split_learning_tpu/runtime/context.py": {
        "_drive_columns": "loops",
    },
}

#: attribute names of the jitted ops a ShardRunner / pipeline exposes
_JIT_OPS = {"fwd", "bwd", "last_step", "whole_step", "apply_update",
            "step"}
_SYNC_CALLS = {"float", "int", "bool"}
_SYNC_ATTRS = {"item", "tolist", "block_until_ready", "device_get"}
_ANNOT_RE = re.compile(r"#\s*slcheck:\s*(.+?)\s*$")


def _annotated(source_lines: list[str], lineno: int, tag: str) -> bool:
    if 0 < lineno <= len(source_lines):
        m = _ANNOT_RE.search(source_lines[lineno - 1])
        return bool(m and tag in m.group(1))
    return False


def _is_jnp_expr(node: ast.AST) -> bool:
    """Does this expression root in a jnp./jax. call?"""
    while isinstance(node, ast.Call):
        node = node.func
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, ast.Name) and node.id in ("jnp", "jax")


class _HotLoopVisitor(ast.NodeVisitor):
    def __init__(self, rel: str, fn_name: str, mode: str,
                 source_lines: list[str]):
        self.rel = rel
        self.fn_name = fn_name
        self.mode = mode
        self.lines = source_lines
        self.loop_depth = 0
        self.device_names: set[str] = set()
        self.findings: list[Finding] = []

    def _note_assign(self, node: ast.Assign) -> None:
        val = node.value
        is_dev = False
        if isinstance(val, ast.Call):
            f = val.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            is_dev = name in _JIT_OPS or _is_jnp_expr(val)
        if is_dev:
            for t in node.targets:
                for n in ([t] if isinstance(t, ast.Name)
                          else list(getattr(t, "elts", []))):
                    if isinstance(n, ast.Name):
                        self.device_names.add(n.id)

    def _in_hot_region(self) -> bool:
        return self.mode == "all" or self.loop_depth > 0

    def _flag(self, node: ast.AST, what: str) -> None:
        if _annotated(self.lines, node.lineno, "allow-sync"):
            return
        self.findings.append(Finding(
            "JX001", self.rel, node.lineno, self.fn_name,
            f"implicit device->host sync in hot loop: {what}"))

    def visit_Assign(self, node: ast.Assign):
        self._note_assign(node)
        self.generic_visit(node)

    def _visit_loop(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_For = visit_While = _visit_loop

    def _arg_is_device(self, arg: ast.AST) -> bool:
        if _is_jnp_expr(arg):
            return True
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Name) and sub.id in self.device_names:
                return True
        return False

    def visit_Call(self, node: ast.Call):
        if self._in_hot_region():
            f = node.func
            if isinstance(f, ast.Name) and f.id in _SYNC_CALLS \
                    and node.args and self._arg_is_device(node.args[0]):
                self._flag(node, f"{f.id}({ast.unparse(node.args[0])})")
            elif isinstance(f, ast.Attribute):
                if f.attr in _SYNC_ATTRS and self._arg_is_device(
                        node.args[0] if node.args else f.value):
                    self._flag(node, f"{ast.unparse(f)}(...)")
                elif f.attr == "asarray" and isinstance(f.value, ast.Name) \
                        and f.value.id == "np" and node.args \
                        and self._arg_is_device(node.args[0]):
                    self._flag(node,
                               f"np.asarray({ast.unparse(node.args[0])})")
                elif f.attr == "jit":
                    self.findings.append(Finding(
                        "JX006", self.rel, node.lineno, self.fn_name,
                        "jax.jit called inside a loop body: every "
                        "iteration builds a fresh wrapper and defeats "
                        "the compile cache"))
        self.generic_visit(node)


def _audit_hot_loops(root: pathlib.Path) -> list[Finding]:
    findings: list[Finding] = []
    for rel, funcs in HOT_FUNCTIONS.items():
        path = root / rel
        source = path.read_text()
        lines = source.splitlines()
        tree = ast.parse(source)
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and node.name in funcs:
                v = _HotLoopVisitor(rel, node.name, funcs[node.name],
                                    lines)
                v.visit(node)
                findings += v.findings
    return findings


# -- donated-then-reused ----------------------------------------------------
# Convention (parallel/pipeline.py make_*_train_step): a step called as
#   params, opt, stats, loss, *_ = step(params, opt, stats, x, labels, rngs)
# donates positions (0, 1, 2); the frozen/LoRA variant
#   t, opt, stats, loss = step(frozen, t, opt, stats, x, labels, rngs)
# donates (1, 2, 3).  Call sites must rebind every donated argument
# from the result tuple IN THE SAME STATEMENT.

def _audit_donation(root: pathlib.Path) -> list[Finding]:
    findings: list[Finding] = []
    rel = "split_learning_tpu/runtime/context.py"
    tree = ast.parse((root / rel).read_text())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        val = node.value
        if not (isinstance(val, ast.Call)
                and isinstance(val.func, ast.Name)
                and val.func.id == "step"):
            continue
        n_args = len(val.args)
        if n_args not in (6, 7):
            continue   # not the train-step convention (e.g. eval step)
        donated = (1, 2, 3) if n_args == 7 else (0, 1, 2)
        targets: set[str] = set()
        for t in node.targets:
            for n in ([t] if isinstance(t, ast.Name)
                      else list(getattr(t, "elts", []))):
                if isinstance(n, ast.Name):
                    targets.add(n.id)
        for pos in donated:
            if pos >= n_args:
                continue
            arg = val.args[pos]
            if isinstance(arg, ast.Name) and arg.id not in targets:
                findings.append(Finding(
                    "JX005", rel, node.lineno, "step-call",
                    f"donated argument {arg.id!r} (position {pos}) is "
                    "not rebound from the step result: the buffer is "
                    "invalid after the call"))
    return findings


# -- round-boundary update donation (JX007) ---------------------------------
# Convention (runtime/aggregate.py): a jitted op whose function takes a
# running-accumulator parameter — named `acc` / `stat_acc` — consumes
# that buffer (the fold adds in place, the fused stage update finishes
# it).  Not donating it doubles the aggregation plane's residency and
# adds a full-stage copy per call.

_UPDATE_BUF_PARAMS = {"acc", "stat_acc"}
_UPDATE_REL = "split_learning_tpu/runtime/aggregate.py"


def _scan_update_donation(source: str, rel: str) -> list[Finding]:
    findings: list[Finding] = []
    tree = ast.parse(source)
    defs = {n.name: n for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "jit" and node.args):
            continue
        fn = node.args[0]
        if isinstance(fn, ast.Lambda):
            params = [a.arg for a in fn.args.args]
        elif isinstance(fn, ast.Name) and fn.id in defs:
            params = [a.arg for a in defs[fn.id].args.args]
        else:
            continue
        positions = [i for i, p in enumerate(params)
                     if p in _UPDATE_BUF_PARAMS]
        if not positions:
            continue
        donated: tuple = ()
        for kw in node.keywords:
            if kw.arg == "donate_argnums":
                try:
                    val = ast.literal_eval(kw.value)
                except ValueError:
                    val = None
                if isinstance(val, int):
                    donated = (val,)
                elif isinstance(val, (tuple, list)):
                    donated = tuple(val)
        missing = [params[i] for i in positions if i not in donated]
        if missing:
            findings.append(Finding(
                "JX007", rel, node.lineno, "jit",
                "round-boundary update buffer(s) "
                f"{missing!r} not in donate_argnums: every fold/update "
                "call allocates a fresh full-stage buffer instead of "
                "updating in place"))
    return findings


def _audit_update_donation(root: pathlib.Path) -> list[Finding]:
    return _scan_update_donation((root / _UPDATE_REL).read_text(),
                                 _UPDATE_REL)


def _audit_update_jaxpr(root: pathlib.Path) -> list[Finding]:
    """Trace the fused sharded stage update (the round-boundary
    program per stage) and audit it like the train ops: no host
    round-trip primitives (JX003), and no fp32-upcast leaving the
    program — a leaf the START will ship as bf16 must come back bf16
    (JX002), or every round fetches (and pins in the shadow) double
    the bytes the wire carries."""
    import jax
    import ml_dtypes
    import numpy as np

    bf16 = np.dtype(ml_dtypes.bfloat16)
    from split_learning_tpu.runtime.aggregate import (
        MeshFoldBackend, _StageFold,
    )

    findings: list[Finding] = []
    be = MeshFoldBackend()
    st = _StageFold(["c0"])
    declared = {"layer0/k": bf16, "layer0/b": np.dtype(np.float32),
                "layer0/step": np.dtype(np.int32)}
    st.dtype = dict(declared)
    st.total_w = 2.0
    st.acc = {
        "layer0/k": be.contrib(np.ones((8, 4), bf16), 2.0),
        "layer0/b": be.contrib(np.ones((4,), np.float32), 2.0),
        "layer0/step": be.contrib(np.asarray(3, np.int32), 2.0),
    }
    base_flat = {"layer0/k": np.ones((8, 4), np.float32),
                 "layer0/b": np.ones((4,), np.float32)}
    params, stats, _ = be.stage_fetch(
        be.stage_update(st, base_flat, {}, 0.9))
    for path, dt in declared.items():
        got = np.asarray(params[path]).dtype
        if got != dt:
            findings.append(Finding(
                "JX002", _UPDATE_REL, 0, "stage_update",
                f"fused update returns {path} as {got} but the START "
                f"wire dtype is {dt}: cast on device before the "
                "fetch"))
    # the program the call above compiled-and-cached, traced abstractly
    for prog in be._fused_cache.values():
        jaxpr = jax.make_jaxpr(
            lambda acc, stat, base, vel: prog(
                acc, stat, base, vel, np.float32(2.0),
                np.float32(0.0), np.float32(0.9)))(
            {p: np.ones((8, 4), np.float32) if p == "layer0/k"
             else (np.ones((4,), np.float32) if p == "layer0/b"
                   else np.float32(6.0))
             for p in declared},
            {}, dict(base_flat),
            {p: np.zeros_like(v) for p, v in base_flat.items()})
        _scan_jaxpr(jaxpr, _UPDATE_REL, "stage_update", findings)
    return findings


# -- jaxpr pass -------------------------------------------------------------

_AUDIT_MODEL = "KWT_SPEECHCOMMANDS"
_AUDIT_KWARGS = {"embed_dim": 16, "num_heads": 2, "mlp_dim": 32}
_AUDIT_INPUT = (2, 40, 98)   # synthetic MFCC batch (data/datasets.py)


def _scan_jaxpr(jaxpr, rel: str, where: str,
                findings: list[Finding]) -> None:
    import jax.numpy as jnp

    seen = set()

    def walk(jx):
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if any(tag in name for tag in
                   ("callback", "infeed", "outfeed")):
                findings.append(Finding(
                    "JX003", rel, 0, where,
                    f"host round-trip primitive {name!r} compiled "
                    "into the step"))
            for sub in eqn.params.values():
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    walk(inner)
        for var in list(jx.invars) + list(jx.outvars):
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt == jnp.float64:
                findings.append(Finding(
                    "JX003", rel, 0, where,
                    "float64 aval in the step jaxpr (x64 drift)"))
                return

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)


def _audit_jaxprs(root: pathlib.Path,
                  wire_dtype: str = "bfloat16") -> list[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from split_learning_tpu.runtime.client import (
        ShardRunner, _cast_for_wire, device_wire_dtype, _wire_np_dtype,
    )

    rel = "split_learning_tpu/runtime/client.py"
    findings: list[Finding] = []
    runner = ShardRunner(_AUDIT_MODEL, 0, -1, {"batch_size": 2},
                         model_kwargs=dict(_AUDIT_KWARGS))
    x = jax.ShapeDtypeStruct(_AUDIT_INPUT, jnp.float32)
    rng = jax.random.key(0)
    variables = jax.eval_shape(
        lambda k: runner.model.init(k, jnp.zeros(_AUDIT_INPUT,
                                                 jnp.float32),
                                    train=False), rng)
    params = variables["params"]
    stats: dict = {}
    frozen: dict = {}
    t = {"lora": {}, "head": params}
    dev_dtype = device_wire_dtype(_wire_np_dtype(wire_dtype))

    def wire_fwd(f, tt, s, xx, k):
        # mirror the hot loop: jitted fwd, then the device-side wire
        # cast that runs before the device->host fetch
        return _cast_for_wire(runner.fwd(f, tt, s, xx, k), dev_dtype)

    jaxpr = jax.make_jaxpr(wire_fwd)(frozen, t, stats, x, rng)
    _scan_jaxpr(jaxpr, rel, "fwd", findings)
    wire_np = _wire_np_dtype(wire_dtype)
    # int8 wire quantizes host-side (QuantLeaf); there is no device
    # cast to audit, so the width check only covers float wires
    wire_width = (None if np.dtype(wire_np) == np.int8
                  else np.dtype(wire_np).itemsize)
    out_shapes = jax.eval_shape(wire_fwd, frozen, t, stats, x, rng)
    for leaf in jax.tree_util.tree_leaves(out_shapes):
        if (wire_width is not None
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and np.dtype(leaf.dtype).itemsize > wire_width):
            findings.append(Finding(
                "JX002", rel, 0, "fwd",
                f"wire-bound activation leaves the device as "
                f"{leaf.dtype} but transport.wire-dtype is "
                f"{wire_dtype}: cast on device before the fetch"))
            break
    # the backward path's input-gradient feeds the wire the same way
    ct = out_shapes

    def wire_bwd(f, tt, s, xx, cc, k):
        gt, gx, new_stats = runner.bwd(f, tt, s, xx, cc, k)
        return _cast_for_wire(gx, dev_dtype)

    jaxpr_b = jax.make_jaxpr(wire_bwd)(frozen, t, stats, x, ct, rng)
    _scan_jaxpr(jaxpr_b, rel, "bwd", findings)
    gx_shapes = jax.eval_shape(wire_bwd, frozen, t, stats, x, ct, rng)
    for leaf in jax.tree_util.tree_leaves(gx_shapes):
        dt = getattr(leaf, "dtype", None)
        if wire_width is not None and dt is not None \
                and jnp.issubdtype(dt, jnp.floating) \
                and np.dtype(dt).itemsize > wire_width:
            findings.append(Finding(
                "JX002", rel, 0, "bwd",
                f"wire-bound input gradient leaves the device as {dt} "
                f"but transport.wire-dtype is {wire_dtype}"))
            break
    # retrace determinism: an identical second trace proves no
    # time/random call leaked into trace time (every retrace would
    # otherwise compile a fresh program)
    again = jax.make_jaxpr(wire_fwd)(frozen, t, stats, x, rng)
    if str(jaxpr) != str(again):
        findings.append(Finding(
            "JX004", rel, 0, "fwd",
            "re-tracing the train step produced a different jaxpr: "
            "trace-time nondeterminism forces recompiles"))
    return findings


def run(root: pathlib.Path, trace: bool = True) -> list[Finding]:
    findings = _audit_hot_loops(root)
    findings += _audit_donation(root)
    findings += _audit_update_donation(root)
    if trace:
        from split_learning_tpu.config import TransportConfig
        wire = TransportConfig().wire_dtype_normalized
        findings += _audit_jaxprs(root, wire)
        findings += _audit_update_jaxpr(root)
    return findings
