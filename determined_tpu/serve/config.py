"""ServeConfig: the sizing knobs of one serving replica.

Every shape the jitted prefill/decode steps trace over comes from here —
lane count, prompt padding, block-table width — so the config is also the
retrace contract: two requests that differ only in length run through the
same compiled program.  ``docs/serving.md`` explains how to size the cache
(``num_blocks``) against HBM and expected sequence lengths.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from determined_tpu.config.experiment import InvalidExperimentConfig


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    # ---- paged KV cache ---------------------------------------------------
    #: tokens per cache block (vLLM-style fixed-size pages)
    block_size: int = 16
    #: physical blocks in the pool; block 0 is the scratch block padded
    #: writes land in, so usable capacity is (num_blocks - 1) * block_size
    num_blocks: int = 256
    # ---- continuous batching ----------------------------------------------
    #: decode lanes: max sequences in flight per step (static batch shape)
    max_batch: int = 8
    #: the longest prompt admitted (a prefill costs its prompt's chunks, not
    #: this: ``prefill_chunk``)
    max_prompt_len: int = 128
    #: cap on tokens generated per request (requests may ask for fewer)
    max_new_tokens: int = 64
    # ---- admission --------------------------------------------------------
    #: bounded request queue depth; a full queue rejects with 429
    queue_depth: int = 16
    # ---- fast path --------------------------------------------------------
    #: share full KV blocks across requests with a common prompt prefix
    #: (content-addressed hash trie in the allocator); admission then only
    #: prefills the un-cached suffix.  Off restores the PR-9 data path.
    prefix_cache: bool = True
    #: paged decode attention (``ops/paged_attention.py``): any value above
    #: 0 makes a decode step read each lane's live blocks from the pool
    #: where it lies and fold them into a float32 online softmax: the
    #: Pallas kernel on a TPU when head_dim is a multiple of 128 and
    #: block_size fills the cache dtype's sublane tile (16 for bf16), the
    #: same mathematics in ``jax.numpy`` otherwise.  The value only
    #: selects; how many blocks a pass takes is chosen from the shapes.
    #: 0 = gather the whole table every step (the parity tests' oracle).
    #: Must divide blocks_per_seq, as it had to when it was the pass width.
    decode_chunk_blocks: int = 1
    # ---- http / replica ---------------------------------------------------
    host: str = "127.0.0.1"
    port: int = 8001
    #: master heartbeat period (seconds) when registered
    heartbeat_interval_s: float = 2.0
    #: how long a SIGTERM drain waits for in-flight work before giving up
    drain_grace_s: float = 30.0
    # ---- observability ---------------------------------------------------
    #: where ``dtpu serve`` / ``exec/serve_replica`` write the replica's
    #: span timeline (``events.jsonl`` while it runs, ``trace.json`` once
    #: drained); None leaves the process tracer off (``serve/tracing.py``)
    trace_dir: Optional[str] = None

    def __post_init__(self) -> None:
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is scratch), got {self.num_blocks}"
            )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_prompt_len < 1 or self.max_new_tokens < 1:
            raise ValueError("max_prompt_len and max_new_tokens must be >= 1")
        if self.queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got {self.queue_depth}")
        needed = self.blocks_for(self.max_prompt_len + self.max_new_tokens)
        if needed > self.usable_blocks:
            raise ValueError(
                f"cache too small: a worst-case request needs {needed} blocks "
                f"but only {self.usable_blocks} are usable "
                "(raise num_blocks or lower max_prompt_len/max_new_tokens)"
            )
        if self.decode_chunk_blocks < 0:
            raise InvalidExperimentConfig(
                f"decode_chunk_blocks must be >= 0, got {self.decode_chunk_blocks}"
            )
        if self.decode_chunk_blocks and self.blocks_per_seq % self.decode_chunk_blocks:
            # kept from when the value was the width of a pass over the
            # table: a config that was refused then is refused now
            raise InvalidExperimentConfig(
                f"decode_chunk_blocks={self.decode_chunk_blocks} does not divide "
                f"the block-table width ({self.blocks_per_seq} blocks per "
                "sequence); pick a divisor or 0 for the full-table gather"
            )

    # -- derived sizes -------------------------------------------------------

    @property
    def max_seq_len(self) -> int:
        """Longest sequence a lane can hold (prompt + generated)."""
        return self.max_prompt_len + self.max_new_tokens

    @property
    def blocks_per_seq(self) -> int:
        """Block-table width: logical blocks a worst-case sequence spans."""
        return self.blocks_for(self.max_seq_len)

    @property
    def prefill_chunk(self) -> int:
        """Tokens an iteration of the prefill walk takes: a prefill computes
        whole chunks, from the one its first un-cached token lies in to the
        one that ends its prompt."""
        from determined_tpu.models.serving import prefill_chunk_tokens

        return prefill_chunk_tokens(self.block_size, self.max_prompt_len)

    def prefill_chunks(self, prompt_tokens: int, cached_tokens: int = 0) -> int:
        """Narrow chunks the prefill of a prompt computes, past ``cached_tokens`` of it."""
        chunk = self.prefill_chunk
        return -(-prompt_tokens // chunk) - cached_tokens // chunk

    @property
    def prefill_wide(self) -> int:
        """Narrow chunks a WIDE iteration of the walk takes at once; 1 where the
        walk has no wide loop (``max_prompt_len`` too short for one)."""
        from determined_tpu.models.serving import prefill_wide_chunks

        return prefill_wide_chunks(self.prefill_chunk, self.prefill_chunks(self.max_prompt_len) * self.prefill_chunk)

    def prefill_walk(self, per_wide: int, prompt_tokens: int, cached_tokens: int = 0) -> Tuple[int, int]:
        """(wide, narrow) iterations of the walk over those chunks, ``per_wide``
        of them a wide one (``prefill_wide``): each sweeps the weights once."""
        from determined_tpu.models.serving import prefill_walk_chunks

        chunk = self.prefill_chunk
        return prefill_walk_chunks(per_wide, cached_tokens // chunk, -(-prompt_tokens // chunk))

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # scratch block 0 is never allocated

    def blocks_for(self, n_tokens: int) -> int:
        from determined_tpu.serve.kv_cache import blocks_for_tokens

        return blocks_for_tokens(n_tokens, self.block_size)

    @classmethod
    def from_dict(cls, raw: Optional[Dict[str, Any]]) -> "ServeConfig":
        raw = dict(raw or {})
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(raw) - fields
        if unknown:
            raise ValueError(f"unknown serve config keys: {sorted(unknown)}")
        return cls(**raw)
