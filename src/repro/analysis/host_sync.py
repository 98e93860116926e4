"""host-sync: no hidden device synchronisation on hot paths.

jax dispatch is async — the engine's throughput comes from keeping the
device queue full.  Any of ``.item()``, ``float(tracer)``,
``np.asarray(...)`` or ``block_until_ready`` forces the host to wait for
the device, which is invisible in the source and brutal in a profile.  The
rules, scoped to the engine/admission/kernel hot modules:

  * ``.item()`` — never; pull scalars out with ``np.asarray`` ONCE at the
    API boundary, not per-value.
  * ``block_until_ready`` — only inside ``repro.obs.fence()`` (which is
    gated on ``obs.enabled()`` so production dispatch stays async).
  * inside jit-compiled functions and Pallas kernel bodies:
    ``float()``/``int()``/``np.asarray``/``np.array`` — these either sync
    a tracer or fail at trace time; both are bugs.
  * ``jax.jit`` called inside a function body — a fresh jit wrapper per
    call defeats the compile cache (unhashable/unbounded cache keys);
    jit belongs at module scope or behind an explicit cache.
  * a For/While loop whose body both dispatches a kernel and converts the
    result to host (``float``/``np.asarray``) — a per-iteration sync
    barrier; batch the dispatches, convert once after the loop.

Cold paths (summaries, export, CLIs) are out of scope; genuinely needed
syncs on a hot path carry ``# repro: allow[host-sync] <why>``.
"""
from __future__ import annotations

import ast
from typing import List

from .base import Project, Violation, attr_chain, call_leaf

CHECK = "host-sync"

HOT = (
    "src/repro/kernels/",
    "src/repro/core/aqp.py",
    "src/repro/core/aqp_query.py",
    "src/repro/core/aqp_admission.py",
    "src/repro/core/aqp_multid.py",
    "src/repro/core/aqp_ci.py",
    "src/repro/data/aqp_store.py",
)

CONVERTERS = {"float", "int"}
NP_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                 "jax.device_get"}
DISPATCHERS = {
    "batch_query", "batch_query_boxes", "batch_query_grouped",
    "kde_eval", "aqp_batch_sums", "aqp_box_sums", "aqp_grouped_sums",
    "qmc_box_reduce", "rff_density", "lscv_grid_sums", "gh_fused_sum",
    "pairwise_scaled_ksum",
}


def _is_hot(rel: str) -> bool:
    return any(rel == h or (h.endswith("/") and rel.startswith(h))
               for h in HOT)


def _is_jitted(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        chain = attr_chain(dec if not isinstance(dec, ast.Call) else dec.func)
        if "jit" in chain.split("."):
            return True
        if isinstance(dec, ast.Call):  # functools.partial(jax.jit, ...)
            for arg in dec.args:
                if "jit" in attr_chain(arg).split("."):
                    return True
    return False


def _is_kernel_body(fn: ast.FunctionDef) -> bool:
    params = fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
    return any(a.arg.endswith("_ref") for a in params)


def check(project: Project) -> List[Violation]:
    out: List[Violation] = []
    for sf in project.files("src/"):
        if not _is_hot(sf.rel):
            continue

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                if (call_leaf(node) == "item"
                        and isinstance(node.func, ast.Attribute)
                        and not node.args):
                    out.append(Violation(
                        CHECK, sf.rel, node.lineno,
                        ".item() synchronises the device per scalar — "
                        "convert once at the API boundary"))
            if (isinstance(node, ast.Attribute)
                    and node.attr == "block_until_ready"):
                out.append(Violation(
                    CHECK, sf.rel, node.lineno,
                    "block_until_ready outside obs.fence() — fencing must "
                    "stay gated on obs.enabled()"))

        for fn in [n for n in ast.walk(sf.tree)
                   if isinstance(n, ast.FunctionDef)]:
            # jax.jit inside a function body: new wrapper (and compile
            # cache) per call
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    chain = attr_chain(node.func)
                    if chain in ("jax.jit", "jax.pmap"):
                        out.append(Violation(
                            CHECK, sf.rel, node.lineno,
                            f"{chain}() inside {fn.name}() — per-call jit "
                            f"wrappers defeat the compile cache; hoist to "
                            f"module scope or an explicit cache"))

            if _is_jitted(fn) or _is_kernel_body(fn):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    chain = attr_chain(node.func)
                    if (chain in CONVERTERS or chain in NP_CONVERTERS):
                        out.append(Violation(
                            CHECK, sf.rel, node.lineno,
                            f"{chain}() inside traced function {fn.name}() "
                            f"— syncs a tracer to host (or fails to trace)"))

        # per-iteration sync: loop body that dispatches AND converts
        for loop in [n for n in ast.walk(sf.tree)
                     if isinstance(n, (ast.For, ast.While))]:
            dispatches = converts = None
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                chain = attr_chain(node.func)
                leaf = call_leaf(node)
                if leaf in DISPATCHERS:
                    dispatches = node
                if chain in CONVERTERS or chain in NP_CONVERTERS:
                    converts = node
            if dispatches is not None and converts is not None:
                out.append(Violation(
                    CHECK, sf.rel, converts.lineno,
                    f"loop at line {loop.lineno} dispatches a kernel and "
                    f"converts to host every iteration — batch the "
                    f"dispatches, convert once after the loop"))
    return out
