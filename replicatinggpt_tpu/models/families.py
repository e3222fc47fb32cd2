"""ONE place that gives the serving engine a family's programs.

``serve/engine.py`` and ``serve/pages.py`` ask ``family(cfg)`` for the four
paged entry points, the pool constructor and the parameter initialiser of
``cfg.family`` instead of importing ``models.gpt``'s by name; a family that
lacks a program refuses it from inside (``models/exaone_moe.py``,
``models/lfm2_moe.py``, ``models/joyai_llm_flash.py``), ``serve_refusals``
says at the engine's constructor which engine options a family cannot run
under, and why (the family's own ``refusals``), and ``decide_kernel_route``
asks the family whether the kernel fits its windowed steps.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, NamedTuple, Tuple

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
    #: per-slot state the pool dict keeps beside its pages, as ``(kind,
    #: prefix of its entries' names)``: ``window`` rings, ``conv`` state.
    #: Admission reserves no pages for it, page copies skip it, and the
    #: radix cache is refused over it
    slot_entries: Tuple[Tuple[str, str], ...]
    #: the kernel fits the family's mixed and verify steps, or None where
    #: the family has no such step to route:
    #: ``(cfg, page_size, n_pages, itemsize, mesh, qcfg) -> Optional[bool]``
    window_kernel_ok: Callable
    #: engine option (``REFUSABLE``) -> why this family cannot run under it
    refusals: Dict[str, str]


#: the engine options a family may refuse, and when each is asked for
REFUSABLE = {
    "mixed_window": lambda ecfg, drafter: ecfg.decode_window > 1,
    "verify": lambda ecfg, drafter: drafter is not None,
    "prefix_cache": lambda ecfg, drafter: ecfg.prefix_cache,
    "mesh": lambda ecfg, drafter: ecfg.mesh_data > 1 or ecfg.mesh_model > 1,
    "quant": lambda ecfg, drafter: (
        ecfg.kv_quant, ecfg.weight_quant, ecfg.act_quant) != ("none",) * 3,
}

#: the paged kernel of the grouped-query families' decode steps
GQA_KERNEL = "the grouped-query kernel (paged_gqa_attention)"


def family(cfg: ModelConfig) -> Family:
    return _family(cfg.family)


def _served(m, name: str, slot_entries, *, kernel: str, verify: str,
            prefix_cache=None) -> Family:
    """A serve-only family built of ``models/layers.py``'s pieces, from
    its module ``m``: one chip, plain pages, one decode step a launch, no
    windowed step for the kernel to fit (None). ``kernel`` names the paged
    kernel its decode step runs; ``verify`` / ``prefix_cache``: why ITS
    state refuses them (``prefix_cache`` None: the radix cache serves
    it)."""
    refusals = {
        "mixed_window": "decode_window > 1 admits through the mixed "
                        f"prefill+decode window, which {name} does not "
                        "have",
        "verify": verify,
        "mesh": "a serving mesh: parallel/mesh.py has no expert axis and "
                f"{kernel} has no shard_map wrapper",
        "quant": f"quantised pools or weights: {kernel} reads plain pages "
                 f"and quant/weights.py knows GPT-2's leaves, not {name}'s"}
    if prefix_cache is not None:
        refusals["prefix_cache"] = prefix_cache
    return Family(
        name=name, init_params=m.init_params,
        init_paged_kv_pool=m.init_paged_kv_pool,
        prefill_chunk_paged=m.prefill_chunk_paged,
        decode_window_paged=m.decode_window_paged,
        mixed_window_paged=m.mixed_window_paged,
        verify_step_paged=m.verify_step_paged,
        serve_cast_leaves=m.SERVE_CAST_LEAVES,
        step_counters=m.STEP_COUNTERS, slot_entries=slot_entries,
        window_kernel_ok=lambda *a: None, refusals=refusals)


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

        return Family(
            name="gpt", init_params=gpt.init_params,
            init_paged_kv_pool=pool, prefill_chunk_paged=prefill,
            decode_window_paged=gpt.decode_window_paged,
            mixed_window_paged=gpt.mixed_window_paged,
            verify_step_paged=gpt.verify_step_paged,
            serve_cast_leaves=gpt.SERVE_CAST_LEAVES, step_counters=(),
            slot_entries=(), window_kernel_ok=window_ok, refusals={})
    if name == "exaone_moe":
        from . import exaone_moe as m
        return _served(
            m, name, (("window", m.WINDOW_ENTRY_PREFIX),), kernel=GQA_KERNEL,
            verify="speculative verify: a rejected draft's rows cannot be "
                   "taken back out of the window layers' rings",
            prefix_cache="prefix_cache: the radix cache shares full "
                         "layers' pages and cannot restore a window "
                         "layer's ring")
    if name == "lfm2_moe":
        from . import lfm2_moe as m
        return _served(
            m, name, (("conv", m.CONV_ENTRY_PREFIX),), kernel=GQA_KERNEL,
            verify="speculative verify: a rejected draft's columns cannot "
                   "be rolled back out of the conv layers' state (no "
                   "snapshot of it is kept)",
            prefix_cache="prefix_cache: the radix cache shares full "
                         "layers' pages and cannot restore a conv layer's "
                         "state at a shared prefix's end")
    if name == "joyai_llm_flash":
        from . import joyai_llm_flash as m
        # the latent rows are pool pages and nothing is kept a slot: the
        # radix cache shares them as it shares GPT-2's
        return _served(
            m, name, (), kernel="the latent kernel (paged_mla_attention)",
            verify="speculative verify: the family has no verify window "
                   "over its latent pages, and no drafter of its own")
    raise KeyError(f"no model family {name!r}")


def serve_refusals(cfg: ModelConfig, ecfg, drafter=None) -> List[str]:
    """Why this engine configuration cannot serve ``cfg``'s family (empty:
    it can): the family's reason for each option it refuses that ``ecfg``
    asks for. GPT-2 runs under every option the engine has."""
    refuses = family(cfg).refusals
    return [refuses[option] for option, asked in REFUSABLE.items()
            if option in refuses and asked(ecfg, drafter)]
