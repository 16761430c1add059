"""What the serving engine asks of a model: its DESCRIPTION.

:class:`~ray_tpu.serve.engine.DecodeEngine` serves any decoder whose
config object answers ``cfg.decode_programs()`` with a module (or
namespace) of the paged slot-pool programs: the engine binds no model
module by name. Four decoders answer today: the GPT-2 block
(:mod:`ray_tpu.models.gpt_decode`: a page holds keys and values per
head), the latent-attention expert decoder
(:mod:`ray_tpu.models.mla_moe`: a page holds one 576-wide latent a
token in a row of 640 lanes, no head axis), the hybrid
linear-attention expert decoder (:mod:`ray_tpu.models.kda_moe`: one
layer in four keeps grouped keys and values in pages, the others a
fixed recurrent state and a short convolution's tail PER SLOT) and the
shortcut-connected expert decoder (:mod:`ray_tpu.models.scmoe`: TWO
latent attentions a layer, so the latent entry counts ``2 * n_layer``
layers of the one pool; the attention is ``mla_moe``'s, imported).

A description provides, under these names:

``cache_spec(cfg, kv_dtype) -> CacheSpec``
    what one token leaves in a page and what a sequence keeps in its
    slot, per layer that keeps it (below). The ONE place the pool's
    shapes come from: :func:`init_paged_pool`,
    :func:`CacheSpec.bytes_per_page`, :func:`CacheSpec.bytes_per_slot`,
    the engine's handoff shape checks and ``stats()``'s
    ``kv_bytes_per_token`` / ``state_bytes_per_slot`` all read it.
``init_paged_cache``, ``kv_bytes_per_page``, ``shard_params``,
``check_tp``
    the pool, its page cost, the weights' placement and the (cfg, tp)
    validation.
``jit_prefill_into_slot_paged``, ``jit_decode_chunk_slots_paged``
    the two programs every model has, under the names a device trace
    shows (``jit_prefill_into_slot_paged(…``).
``jit_verify_chunk_slots_paged``, ``jit_export_slot_kv_paged``,
``jit_import_slot_kv_paged``
    speculative verify and the KV handoff, or absent.
``max_positions(cfg)``
    the longest sequence the model can place (a learned table's rows;
    a rotary model's declared reach).
``KV_DTYPES``, ``ATTN_KERNELS``
    the pool storage types it has, and the NAMES of its decode
    attention paths: what an ``attn_kernel`` knob may say. A name is
    the model's own and says nothing of how the path is built:
    ``gpt_decode`` has two (``"gather"`` plain XLA, ``"pallas"`` the
    fused kernel); ``mla_moe`` has ONE decode attention under one
    name, ``"gather"``, which is a Pallas kernel over each lane's live
    latent pages wherever Mosaic can address a page and plain XLA over
    the gathered pages where it cannot (the model chooses, by shape);
    ``kda_moe`` has one too, ``"gather"`` (its GQA layers' attention:
    a Pallas kernel over each lane's live pages of keys and values
    wherever Mosaic can address a page and a head, plain XLA over the
    gathered pages where it cannot), and chooses its recurrence's
    kernel by shape the same way.
``decode_attention_fused(cfg, page_size, attn_kernel) -> bool``
    OPTIONAL: whether the chunk program built with these knobs holds a
    fused kernel of its decode step's sequence mixing, WHICHEVER that
    is: ``mla_moe`` answers for its attention over latent pages,
    ``kda_moe`` for TWO kernels, the recurrence on its per-slot state
    and its GQA layers' attention over pages, each taken by its own
    shapes: either one makes the answer true. The engine asks it for
    ``warm_up()["attn_kernel_mode"]`` (``"compiled"`` / ``"interpret"``,
    read off the lowered program, or ``None`` without a kernel) and for
    ``stats()["attn_kernel_dispatches"]``; a description without it
    (``gpt_decode``) is asked by name: ``attn_kernel == "pallas"``.
``UNSUPPORTED``
    ``{engine capability: reason}`` for what this model does not get
    (``"int8"``, ``"tp"``, ``"spec_decode"``, ``"roles"``,
    ``"prefix_cache"``): the engine raises the reason at construction.
    A model that lists ``"prefix_cache"`` gets no prefix cache unless
    asked, and the reason when asked.
``STEP_COUNTERS``
    names of the int32 counters the chunk program returns as a fifth
    output, summed over the chunk's steps (``()``: four outputs); the
    engine adds them into ``stats()`` under those names.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

#: Page-table padding value. Positive and far beyond any real pool size,
#: so a sentinel is out-of-bounds for scatter (write DROPPED, never
#: clamped into someone else's page) while reads clip it to a real page
#: whose garbage the attention mask hides. Never use a negative
#: sentinel: traced negative indices WRAP in jnp indexing.
PT_SENTINEL = 2 ** 30


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One array of the pool: ``per`` ``"token"`` (a row for each of a
    page's positions: ``[L, n_pages, page_size, *shape]``), ``"page"``
    (one row a page, e.g. a quantisation scale: ``[L, n_pages,
    *shape]``) or ``"slot"`` (what a SEQUENCE keeps whatever its
    length, e.g. a recurrent state: ``[L, slots, *shape]``; it belongs
    to the slot, not to a page: no page hash shares it and every
    prefill into the slot rebuilds it). ``n_layer``: how many layers
    keep this entry, where that is not the spec's ``n_layer`` (a model
    whose layers are of two kinds)."""
    name: str
    per: str
    shape: Tuple[int, ...]
    dtype: Any
    n_layer: Optional[int] = None

    def _bytes(self, rows: int) -> int:
        for s in self.shape:
            rows *= s
        return rows * jnp.dtype(self.dtype).itemsize

    def bytes_per_page(self, page_size: int) -> int:
        """Bytes of ONE layer's part of one page (0 for a per-slot
        entry: it lives in no page)."""
        return self._bytes({"token": page_size, "page": 1,
                            "slot": 0}[self.per])

    def bytes_per_slot(self) -> int:
        """Bytes of ONE layer's part of one slot (0 for an entry that
        lives in pages)."""
        return self._bytes(1 if self.per == "slot" else 0)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """Per layer, what a token leaves in a page and what a sequence
    keeps in its slot: the entries' trailing shapes and dtypes, and how
    many layers keep one (``n_layer``, or the entry's own count)."""
    n_layer: int
    entries: Tuple[CacheEntry, ...]

    def entry(self, name: str) -> CacheEntry:
        return next(e for e in self.entries if e.name == name)

    def layers(self, name: str) -> int:
        """How many layers keep the entry ``name``."""
        n = self.entry(name).n_layer
        return self.n_layer if n is None else n

    def bytes_per_page(self, page_size: int) -> int:
        """HBM bytes ONE physical page costs across all layers — the
        unit the engine's page budget is denominated in."""
        return sum(self.layers(e.name) * e.bytes_per_page(page_size)
                   for e in self.entries)

    def bytes_per_slot(self) -> int:
        """HBM bytes ONE slot's per-slot entries cost across all layers
        (0 for a model that keeps everything in pages)."""
        return sum(self.layers(e.name) * e.bytes_per_slot()
                   for e in self.entries)

    def token_shape(self, name: str, tokens: int) -> Tuple[int, ...]:
        """``[L, tokens, *shape]``: a per-token entry laid out over a
        contiguous run of tokens (the handoff's ship order)."""
        return (self.layers(name), tokens) + self.entry(name).shape


def init_paged_pool(spec: CacheSpec, slots: int, n_pages: int,
                    page_size: int) -> Dict[str, Any]:
    """Zeroed pool arrays for ``spec`` (pages, and the per-slot entries
    beside them) plus the per-slot ``pos``."""
    cache = {}
    for e in spec.entries:
        lead = {"token": (n_pages, page_size), "page": (n_pages,),
                "slot": (slots,)}[e.per]
        cache[e.name] = jnp.zeros((spec.layers(e.name),) + lead + e.shape,
                                  e.dtype)
    cache["pos"] = jnp.zeros((slots,), jnp.int32)
    return cache


def decode_programs(cfg):
    """The description of ``cfg``'s model (module docstring)."""
    try:
        return cfg.decode_programs()
    except AttributeError:
        raise TypeError(
            f"{type(cfg).__name__} does not describe a decoder the "
            f"engine can serve: it has no decode_programs()") from None
