"""Fused multi-LoRA serving engine: batched prefill + decode over one
frozen backbone with per-request adapter routing (DESIGN.md §13).

The batch layout is the serving twin of the training FusedBatcher:

  * requests SORT BY ADAPTER into contiguous segments (the ragged
    kernels' job-major contract) and each segment's row count pads to
    the kernel row granule — ``block_t`` rows for the Pallas path
    (decode tokens arrive one per row, so rows ARE the token tile),
    1 for the XLA/ref paths;
  * prompts RIGHT-pad to a ``block_t``-aligned width.  Right padding
    makes prefill exact for free: token at column c attends columns
    <= c, all real, and column index == absolute position.  Each
    request's first sampled token reads ``logits[row, len_r - 1]``;
  * decode then runs with PER-ROW positions: each row writes its KV at
    its own depth (``cache_update`` scatter), ropes at its own absolute
    position, and masks keys beyond its own frontier
    (``chunked_attention`` per-row kv_len) — so a fused batch of
    requests at ragged depths decodes exactly like each would solo;
  * the KV buffer pads to ``block_t`` alignment past
    ``prompt_width + max_new`` (core/jobs.tile_rows' granule logic).

One jitted ``generate`` serves both phases — prefill is the same
``decode_step`` at width S — and the whole decode loop is a
``lax.scan``, so a batch costs ONE dispatch and ONE host sync (the
seed's per-token ``np.asarray`` round-trip and duplicate
``make_serve_step`` compiles are gone).  Per-request ``max_new_tokens``
and stop tokens truncate each returned row.

Recurrent mixers (ssd/rglru) and ring caches (local_attn sliding
windows) are rejected at construction: right-padded prefill would fold
pad tokens into a recurrent state, and ring count-masking breaks under
per-row depths.  Position-indexed caches (attn, mla) serve exactly.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.lora import MultiLoRA
from repro.kernels.ops import kernel_defaults
from repro.models import model as M
from repro.serve.pool import AdapterPool, FusedAdapters


def _align(n: int, m: int) -> int:
    """Round *n* up to a multiple of *m* (the tile_rows granule rule)."""
    return ((n + m - 1) // m) * m


@dataclass
class ServeRequest:
    """One inference request routed to a published adapter by name."""
    prompt: np.ndarray                # (len,) int32 token ids
    adapter: str                      # name in the AdapterPool
    max_new_tokens: int = 16
    stop_token: Optional[int] = None  # truncate at (and including) this id


@dataclass
class ServeResult:
    adapter: str
    prompt_len: int
    tokens: np.ndarray                # (n,) generated ids, n <= max_new_tokens


@dataclass
class ServeEngine:
    """Batched multi-adapter serving over one backbone + adapter pool."""
    cfg: ModelConfig
    params: dict
    pool: AdapterPool
    # fused-LoRA kernel impl and token tile; None = the platform's
    # (ops.kernel_defaults: pallas/128 on TPU, xla/8 elsewhere)
    impl: Optional[str] = None
    block_t: Optional[int] = None
    greedy: bool = True
    # int8 frozen backbone for serving (models/quant): halves the
    # resident weight bytes AND the per-token weight streaming — decode
    # is the memory-bound regime where that is ~the whole step.  None =
    # keep the params' dtype (already-quantized trees pass through).
    quantize: Optional[str] = None

    _gen_cache: Dict[tuple, Callable] = field(default_factory=dict)

    def __post_init__(self):
        cfg = self.cfg
        self.impl, self.block_t = kernel_defaults(self.impl, self.block_t,
                                                  cpu_impl="xla")
        if self.quantize is not None:
            from repro.models import quant
            self.params = quant.quantize_params(self.params, self.quantize)
        if not cfg.causal:
            raise ValueError("serving needs a causal decoder config")
        if cfg.family in ("audio", "vlm"):
            raise ValueError(
                f"serving engine takes token prompts; family={cfg.family!r} "
                "frontends are not routable per-request")
        for seg in M.segment_plan(cfg):
            for spec in seg.specs:
                if spec.mixer not in ("attn", "mla"):
                    raise ValueError(
                        f"mixer {spec.mixer!r} keeps recurrent/ring state; "
                        "the fused serving engine needs position-indexed "
                        "caches (attn/mla)")
        if not self.greedy:
            raise NotImplementedError("only greedy decoding is implemented")

    # ------------------------------------------------------------- serve
    def serve(self, requests: Sequence[ServeRequest]) -> List[ServeResult]:
        """Run one fused batch; results come back in request order."""
        assert requests, "serve needs at least one request"
        for r in requests:
            assert len(r.prompt) >= 1, "empty prompt"
            assert r.max_new_tokens >= 1, "max_new_tokens must be >= 1"
        names = tuple(sorted({r.adapter for r in requests}))
        fused = self.pool.acquire(names)
        k_of = {n: k for k, n in enumerate(names)}

        # adapter-major row layout, segment rows padded to the granule
        granule = self.block_t if self.impl == "pallas" else 1
        rows: List[int] = []
        row_req: List[Optional[int]] = []   # request index per row
        for k, n in enumerate(names):
            idxs = [i for i, r in enumerate(requests) if r.adapter == n]
            n_rows = _align(len(idxs), granule)
            rows.append(n_rows)
            row_req.extend(idxs + [None] * (n_rows - len(idxs)))
        B = sum(rows)

        max_new = max(r.max_new_tokens for r in requests)
        S = _align(max(len(r.prompt) for r in requests), self.block_t)
        buf = _align(S + max_new, self.block_t)

        tokens = np.zeros((B, S), np.int32)
        lens = np.ones((B,), np.int32)
        ids = np.zeros((B,), np.int32)
        off = 0
        for k, n_rows in enumerate(rows):
            ids[off:off + n_rows] = k
            off += n_rows
        for row, ri in enumerate(row_req):
            if ri is None:
                continue                     # pad row: 1 zero token
            p = np.asarray(requests[ri].prompt, np.int32)
            tokens[row, :len(p)] = p         # RIGHT-pad
            lens[row] = len(p)

        gen = self._generate(B, S, buf, max_new, tuple(rows), fused.layout)
        out = np.asarray(gen(self.params, fused.adapters,
                             jnp.asarray(tokens), jnp.asarray(ids),
                             fused.ranks, fused.scalings,
                             jnp.asarray(lens)))     # one host sync

        results: List[Optional[ServeResult]] = [None] * len(requests)
        for row, ri in enumerate(row_req):
            if ri is None:
                continue
            r = requests[ri]
            toks = out[row, :r.max_new_tokens]       # per-request truncation
            if r.stop_token is not None:
                hit = np.nonzero(toks == r.stop_token)[0]
                if hit.size:
                    toks = toks[:hit[0] + 1]
            results[ri] = ServeResult(adapter=r.adapter,
                                      prompt_len=len(r.prompt),
                                      tokens=np.array(toks))
        return results  # type: ignore[return-value]

    # ---------------------------------------------------------- generate
    def _generate(self, B: int, S: int, buf: int, max_new: int,
                  rows: Tuple[int, ...], layout) -> Callable:
        """One jitted prefill+decode program per (shape, layout) key."""
        key = (B, S, buf, max_new, rows, layout)
        fn = self._gen_cache.get(key)
        if fn is not None:
            return fn
        cfg, impl, block_t = self.cfg, self.impl, self.block_t
        seg_rows, eq = max(rows), len(set(rows)) == 1

        def gen(params, adapters, tokens, ids, ranks, scalings, lens):
            lora = MultiLoRA(adapter_ids=ids, ranks=ranks,
                             scalings=scalings, impl=impl, block_t=block_t,
                             seg_rows=seg_rows, equal_segments=eq,
                             layout=layout, rows_all=rows)
            caches = M.init_caches(cfg, B, buf, ring=False)
            # prefill: same decode_step at width S, static pos 0 (right
            # padding makes column index == absolute position)
            logits, caches = M.decode_step(cfg, params, adapters, lora,
                                           tokens, 0, caches,
                                           logits_at=lens - 1)
            first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

            def body(carry, _):
                caches, tok, pos = carry
                lg, caches = M.decode_step(cfg, params, adapters, lora,
                                           tok[:, None], pos, caches)
                nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
                return (caches, nxt, pos + 1), nxt

            if max_new > 1:
                _, rest = jax.lax.scan(body, (caches, first, lens),
                                       None, length=max_new - 1)
                toks = jnp.concatenate([first[None], rest], axis=0)
            else:
                toks = first[None]
            return toks.T                               # (B, max_new)

        fn = jax.jit(gen)
        self._gen_cache[key] = fn
        return fn
