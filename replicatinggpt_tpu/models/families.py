"""ONE place that gives the serving engine a family's programs.

``serve/engine.py`` and ``serve/pages.py`` ask ``family(cfg)`` for the four
paged entry points, the pool constructor and the parameter initialiser of
``cfg.family`` instead of importing ``models.gpt``'s by name; a family that
lacks a program refuses it from inside (``models/exaone_moe.py``),
``serve_refusals`` says at the engine's constructor which engine options a
family cannot run under, and why, and ``decide_kernel_route`` asks the
family whether the kernel fits its windowed steps.
"""

from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Tuple

from ..config import ModelConfig


class Family(NamedTuple):
    name: str
    init_params: Callable
    init_paged_kv_pool: Callable   # (cfg, n_pages, page_size, dtype=, quant=, n_slots=)
    prefill_chunk_paged: Callable  # (params, idx, offset, limit, table_row, slot, cache, cfg, shardings=)
    decode_window_paged: Callable
    mixed_window_paged: Callable
    verify_step_paged: Callable
    #: leaf names the entry points read only as ``leaf.astype(compute
    #: dtype)``: the engine serves a tree that holds them cast once
    serve_cast_leaves: Tuple[str, ...]
    #: what a decode window's token block counts in its trailing columns
    step_counters: Tuple[str, ...]
    #: pool-dict entries with this prefix are per-slot state, not pool pages
    slot_entry_prefix: str
    #: the kernel fits the family's mixed and verify steps, or None where
    #: the family has no such step to route:
    #: ``(cfg, page_size, n_pages, itemsize, mesh, qcfg) -> Optional[bool]``
    window_kernel_ok: Callable


def family(cfg: ModelConfig) -> Family:
    return _family(cfg.family)


@functools.lru_cache(maxsize=None)
def _family(name: str) -> Family:
    if name == "gpt":
        from . import gpt

        def pool(cfg, n_pages, page_size, dtype=None, quant=None,
                 n_slots=0):
            return gpt.init_paged_kv_pool(cfg, n_pages, page_size,
                                          dtype=dtype, quant=quant)

        def prefill(params, idx, offset, limit, table_row, slot, cache,
                    cfg, *, shardings=None):
            return gpt.prefill_chunk_paged(params, idx, offset, limit,
                                           table_row, cache, cfg,
                                           shardings=shardings)

        def window_ok(cfg, page_size, n_pages, itemsize, mesh, qcfg):
            from ..ops import paged_pallas
            return paged_pallas.mixed_step_kernel_ok(
                cfg.n_head, cfg.head_dim, page_size, itemsize, mesh=mesh,
                kv_quant=qcfg.kv_dtype, granularity=qcfg.granularity,
                n_pages=n_pages)

        return Family("gpt", gpt.init_params, pool, prefill,
                      gpt.decode_window_paged, gpt.mixed_window_paged,
                      gpt.verify_step_paged, gpt.SERVE_CAST_LEAVES, (),
                      "\0", window_ok)
    if name == "exaone_moe":
        from . import exaone_moe as m
        return Family("exaone_moe", m.init_params, m.init_paged_kv_pool,
                      m.prefill_chunk_paged, m.decode_window_paged,
                      m.mixed_window_paged, m.verify_step_paged,
                      m.SERVE_CAST_LEAVES, m.STEP_COUNTERS,
                      m.WINDOW_ENTRY_PREFIX, lambda *a: None)
    raise KeyError(f"no model family {name!r}")


def serve_refusals(cfg: ModelConfig, ecfg, drafter=None) -> List[str]:
    """Why this engine configuration cannot serve ``cfg``'s family (empty:
    it can). GPT-2 runs under every option the engine has."""
    if cfg.family == "gpt":
        return []
    why = []
    if ecfg.decode_window > 1:
        why.append("decode_window > 1 admits through the mixed "
                   "prefill+decode window, which exaone_moe does not have")
    if drafter is not None:
        why.append("speculative verify: a rejected draft's rows cannot be "
                   "taken back out of the window layers' rings")
    if ecfg.prefix_cache:
        why.append("prefix_cache: the radix cache shares full layers' "
                   "pages and cannot restore a window layer's ring")
    if ecfg.mesh_data > 1 or ecfg.mesh_model > 1:
        why.append("a serving mesh: parallel/mesh.py has no expert axis "
                   "and the grouped-query kernel has no shard_map wrapper")
    if (ecfg.kv_quant, ecfg.weight_quant, ecfg.act_quant) != ("none",) * 3:
        why.append("quantised pools or weights: the grouped-query kernel "
                   "reads plain pages and quant/weights.py knows GPT-2's "
                   "leaves")
    return why
