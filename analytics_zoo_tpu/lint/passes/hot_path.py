"""zoolint pass ``hot-path-sync``: the hand-curated host hot-path policy.

Ported from ``scripts/check_hot_path_syncs.py`` (which is now a thin shim
over this module). The six policy families — estimator dispatch loops,
FeatureSet batch staging, DeviceFeed eval adaptation, sharded-embedding
exchange bodies, the slot decode engine, and the paged/speculative decode
bodies — keep their exact legacy semantics here, table-driven: each row
names the file, the functions, the extra banned ``np.*`` attrs, whether
Python loops are banned outright, and the scope (whole body vs loop
bodies only).

The table stays the right tool for HOST-side staging rules (``_gather``
must route copies through ``np.take(out=)``, ``masked_eval_batches`` must
not rebuild its arange mask — allocation policies no trace analysis can
infer). Device-side rows are additionally *rediscovered automatically* by
the ``jit-host-sync`` pass, which polices the whole traced closure, so the
next decode/embedding PR is covered before anyone edits this table.
"""
from __future__ import annotations

import ast
import os
import sys
from typing import List, Optional, Sequence, Tuple

from ..core import (Finding, LintPass, Project, REPO_ROOT, get_project,
                    register_pass)

ESTIMATOR_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "estimator",
                            "estimator.py")
FEATURESET_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "feature",
                             "featureset.py")
DEVICE_FEED_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "feature",
                              "device_feed.py")
EMBEDDING_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "parallel",
                            "embedding.py")
DECODE_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "ops", "decode.py")
LM_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "capture", "lm.py")
SERVER_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "serving",
                         "server.py")
FLEET_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "serving",
                        "fleet.py")
ENGINE_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "xshard",
                         "engine.py")
PIPELINE_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "parallel",
                           "pipeline.py")
RING_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "parallel",
                       "ring_attention.py")
MOE_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "parallel",
                      "moe.py")

#: model-parallel traced bodies. The pipeline scan bodies run once per
#: tick inside ``lax.scan`` under ``shard_map``, the ring bodies once per
#: ppermute hop, the MoE exchange once per step — all pure device code:
#: loop-free outright (scan/ppermute replace Python iteration), no host
#: syncs, no ``np.*`` staging, no one_hot densification.
PIPELINE_BODIES = ("pipeline_apply", "_pipe_fwd_body", "_pipe_1f1b_body")
# ulysses_attention is deliberately NOT a row: it is a per-shard body the
# CALLER wraps in shard_map, so the jit-boundary pass has no package-level
# trace site to auto-discover it from (the discovery-coverage invariant in
# tests/test_zoolint.py would break); the ring bodies below are reached
# through ring_self_attention/ring_context's own shard_map wrappers.
RING_BODIES = ("ring_attention", "ring_masked_context")
MOE_BODIES = ("_expert_exchange",)

EMBED_BODIES = ("_routing", "_lookup_body", "_lookup_bwd_body",
                "_update_body")

EMBED_KERNELS_PY = os.path.join(REPO_ROOT, "analytics_zoo_tpu", "ops",
                                "embedding_kernels.py")

#: fused embedding kernels (ops/embedding_kernels.py). The KERNEL_BODIES
#: are the per-row hot cores — the pallas kernel bodies and the fused
#: lookup/pool/backward primitives the engine and layers trace per step:
#: loop-free outright (fori_loop is a traced call, not a Python loop), no
#: one_hot densification, no host syncs. The WRAPPERS (multi-table
#: dispatch, table quantization) may loop over the static table count but
#: still must not sync or densify.
EMBED_KERNEL_BODIES = ("gather_rows", "gather_rows_clip", "segment_grads",
                       "scatter_rows", "gather_pool", "gather_pool_int8",
                       "_gather_pool_ref", "_gather_kernel",
                       "_gather_int8_kernel", "_gather_pool_kernel",
                       "_scatter_add_kernel")
EMBED_KERNEL_WRAPPERS = ("multi_table_lookup", "quantize_table",
                         "fused_enabled")

SLOT_OPS = ("init_slot_cache", "slot_join", "slot_evict", "slot_insert",
            "slot_attention")

PAGED_OPS = ("init_paged_pool", "page_table_set", "page_table_clear",
             "page_copy", "_page_positions", "_paged_write", "paged_gather",
             "paged_insert", "paged_attention", "paged_verify_attention",
             "paged_decode_context", "paged_pages_read", "paged_prefix_kv",
             "spec_accept_greedy", "_spec_accept_sampled")
#: the paged decode kernel's pallas body (ops/decode.py): it traces only
#: inside ``pl.pallas_call``, which is no discovery root, so the table
#: polices it; its page copies are ``fori_loop``s, not Python loops
PAGED_KERNEL_BODIES = ("_paged_decode_kernel",)

HOT_FUNCS = ("evaluate", "_evaluate_direct", "_evaluate_direct_exact",
             "predict")

#: XShard ETL engine bodies. The KERNELS are the per-row-scale vector
#: cores (hash mixing, bucket reorder, join match, handoff scatter):
#: loop-free outright. The TASKS are the exchange/partition/gather/
#: combine bodies: loops there are column/source-count sized and legal,
#: but host syncs and full-frame ``pd.concat`` are not.
ETL_KERNELS = ("_mix64", "_bucket_order", "_join_match", "_stack_into",
               "_exchange_task")
ETL_TASKS = ("_gather_dest", "_filter_task", "_groupby_task", "_join_task",
             "_handoff_task", "_take_cols_into")

#: policy rows: (path, class name or None for module level, function names,
#: extra banned np.<attr> calls, ban per-record loops?, scope)
#: scope "loops" = only loop bodies inside the function are policed;
#: scope "body"  = the whole function body is policed (innermost hot funcs)
_CHECKS: List[Tuple[str, Optional[str], Sequence[str], Sequence[str],
                    bool, str]] = [
    (ESTIMATOR_PY, "Estimator", HOT_FUNCS, (), False, "loops"),
    (FEATURESET_PY, "FeatureSet", ("_gather",), ("asarray",), True, "body"),
    (FEATURESET_PY, "LazyTransformFeatureSet",
     ("train_iterator", "eval_iterator", "_transformed_batches",
      "_cached_batches"), (), False, "loops"),
    (DEVICE_FEED_PY, None, ("masked_eval_batches",), ("arange",), False,
     "loops"),
    (DEVICE_FEED_PY, None, ("_produce",), (), False, "loops"),
    (EMBEDDING_PY, None, EMBED_BODIES, (), True, "body"),
    (EMBED_KERNELS_PY, None, EMBED_KERNEL_BODIES, (), True, "body"),
    (EMBED_KERNELS_PY, None, EMBED_KERNEL_WRAPPERS, (), False, "body"),
    (DECODE_PY, None, SLOT_OPS, (), True, "body"),
    (DECODE_PY, None, PAGED_OPS, (), True, "body"),
    (DECODE_PY, None, PAGED_KERNEL_BODIES, (), True, "body"),
    (LM_PY, "TransformerLM",
     ("slot_step", "prefill_kv", "paged_slot_step", "verify_step",
      "prefill_kv_suffix"), (), False, "body"),
    (SERVER_PY, "GenerativeServing",
     ("_dispatch_step", "_insert_request_paged", "_insert_request_spec",
      "_insert_suffix_paged", "_copy_page_device", "_evict_slots"),
     (), True, "body"),
    # the fleet router's placement scoring runs once per routed request:
    # it must stay a single vectorized pass over the instance-gauge
    # arrays — no host syncs, no per-request Python loop over instances
    (FLEET_PY, None, ("_score_instances",), (), True, "body"),
    (ENGINE_PY, None, ETL_KERNELS, (), True, "body"),
    (ENGINE_PY, None, ETL_TASKS, (), False, "body"),
    (PIPELINE_PY, None, PIPELINE_BODIES, (), True, "body"),
    (RING_PY, None, RING_BODIES, (), True, "body"),
    (MOE_PY, None, MOE_BODIES, (), True, "body"),
]


def _banned_call(node: ast.Call, np_attrs: Sequence[str] = ("asarray",)
                 ) -> str:
    f = node.func
    if isinstance(f, ast.Name) and f.id == "float":
        return "float()"
    if isinstance(f, ast.Name) and f.id == "one_hot":
        return "one_hot()"
    if isinstance(f, ast.Attribute):
        if f.attr == "one_hot":
            return "one_hot()"
        base = f.value
        if (f.attr in np_attrs and isinstance(base, ast.Name)
                and base.id in ("np", "numpy")):
            return f"{base.id}.{f.attr}()"
        if (f.attr == "concat" and isinstance(base, ast.Name)
                and base.id in ("pd", "pandas")):
            # a full-frame concat in a policed body is the seed-era
            # gather-everything antipattern the ETL engine exists to kill
            return f"{base.id}.concat()"
        if (f.attr == "device_get" and isinstance(base, ast.Name)
                and base.id == "jax"):
            return "jax.device_get()"
        if f.attr == "block_until_ready":
            return ".block_until_ready()"
    return ""


def _iter_functions(tree: ast.Module, cls: Optional[str],
                    names: Sequence[str]):
    if cls is None:
        for node in tree.body:
            if isinstance(node, ast.FunctionDef) and node.name in names:
                yield node
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            for fn in node.body:
                if isinstance(fn, ast.FunctionDef) and fn.name in names:
                    yield fn


def _scan_stmts(stmts, np_attrs, out, fn_name):
    for stmt in stmts:
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.Call):
                what = _banned_call(sub, np_attrs)
                if what:
                    out.append((fn_name, sub.lineno, what))


def _check_file(path: str, cls: Optional[str], names: Sequence[str],
                extra_np: Sequence[str], ban_loops: bool, scope: str
                ) -> List[Tuple[str, int, str]]:
    tree = get_project().ast_for(path)
    np_attrs = ("asarray",) + tuple(extra_np)
    violations: List[Tuple[str, int, str]] = []
    for fn in _iter_functions(tree, cls, names):
        if scope == "body":
            _scan_stmts(fn.body, np_attrs, violations, fn.name)
            if ban_loops:
                for sub in ast.walk(fn):
                    if isinstance(sub, (ast.For, ast.While, ast.AsyncFor,
                                        ast.ListComp, ast.SetComp,
                                        ast.DictComp, ast.GeneratorExp)):
                        violations.append(
                            (fn.name, sub.lineno, "per-record Python loop"))
            continue
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.While, ast.AsyncFor)):
                continue
            _scan_stmts(loop.body + loop.orelse, np_attrs, violations,
                        fn.name)
    return violations


def policed_functions() -> set:
    """All function names the policy table polices (the legacy hand-listed
    coverage the ``jit-host-sync`` discovery must dominate)."""
    return {fn for row in _CHECKS for fn in row[2]}


def check(path: Optional[str] = None
          ) -> List[Tuple[str, str, int, str]]:
    """Return ``(file, function, line, what)`` violations; empty = clean.
    With an explicit ``path`` only the Estimator dispatch-loop policy runs
    against that file (self-test hook)."""
    if path is not None:
        return [(path, fn, line, what) for fn, line, what in
                _check_file(path, "Estimator", HOT_FUNCS, (), False,
                            "loops")]
    out: List[Tuple[str, str, int, str]] = []
    for (p, cls, names, extra_np, ban_loops, scope) in _CHECKS:
        out.extend((p, fn, line, what) for fn, line, what in
                   _check_file(p, cls, names, extra_np, ban_loops, scope))
    return out


@register_pass
class HotPathPass(LintPass):
    id = "hot-path-sync"
    title = "hand-curated hot-path sync/loop/allocation policy"
    rationale = (
        "the data-plane, eval/predict, embedding-exchange and decode hot "
        "paths must stay free of per-batch host syncs, per-record Python "
        "and per-batch allocation — regressions are invisible to "
        "functional tests and only a healthy BENCH round would notice")

    def run(self, project: Project) -> List[Finding]:
        return [
            Finding(path, line, self.id,
                    f"{what} inside the hot path of {fn}",
                    "route syncs behind the dispatch frontier / drain "
                    "after the loop; keep per-batch staging vectorized")
            for path, fn, line, what in check()
        ]


def main() -> int:
    violations = check()
    if not violations:
        print("hot-path sync lint: clean")
        return 0
    for path, fn, line, what in violations:
        print(f"{path}:{line}: {what} inside the hot path of {fn} — "
              f"route syncs behind the dispatch frontier / drain after "
              f"the loop, and keep per-batch staging vectorized",
              file=sys.stderr)
    return 1
