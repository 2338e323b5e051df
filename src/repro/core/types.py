"""Shared config/state types for the memory-augmented cores.

All state is fixed-shape and jit/scan friendly. Sparse quantities use the
fixed-K "ELL" layout: an int32 index tensor plus a float value tensor of the
same leading shape (see DESIGN.md §2 — CSR does not map to TPU).

Scratch-row memory layout
-------------------------
The sparse cores (SAM, SDNC, the LM memory layer) carry their memory as a
**persistent (B, N+1, W) buffer**: rows [0, N) are the logical memory, row N
is a write-scratch row that the Pallas scatter kernels use to park duplicate
write indices under input/output aliasing. `last_access` is carried as
(B, N+1) with the scratch entry pinned to ``LA_SCRATCH`` (int32 max) so LRA
selection can never pick it. The scratch row is *never read*: every sweep
(top-K similarity, LRA selection) addresses only the logical N rows
(``valid_n=`` in `repro.kernels.ops`), so its contents never influence read
outputs, usage, or gradients. Keeping the row in the state — instead of
padding/slicing around every kernel call — removes an O(N·W) copy from each
step, which is what makes the per-step cost O(J·W) as the paper claims.
See docs/memory-model.md.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Number of write-scratch rows appended past the logical memory (row N).
SCRATCH_ROWS = 1
# `last_access` value pinned on the scratch row: int32 max, so the scratch
# row can never win an LRA argmin even if a sweep forgets to exclude it.
LA_SCRATCH = 2 ** 31 - 1
# Field names of the slot-dimension state leaves — the single source for
# every consumer that must recognize a memory/usage buffer structurally:
# the mem-shard layout transforms and sharding specs (distributed/
# mem_shard.py) and the checkpoint migration/re-layout shims
# (checkpoint/ckpt.py). A new slot-sharded state field must be added HERE
# so the live transforms and the checkpoint path cannot drift apart.
# ``mem_scale`` is the per-row f32 dequantization scale carried alongside
# int8 memory rows (mem_dtype="int8"): it shards, re-lays-out, and
# checkpoints with the slots it scales.
SLOT_LEAVES = frozenset({"memory", "last_access", "usage", "mem_scale"})
# Field names of the ANN index leaves (ANNState). Like SLOT_LEAVES, the
# single source shared by the mem-shard sharding specs (the LSH bucket
# tables shard over their partition dimension) and the checkpoint
# re-layout/migration shims.
ANN_LEAVES = frozenset({"buckets", "cursor"})


def has_scratch_row(num_slots: int, buf_rows: int) -> bool:
    """True when a buffer with `buf_rows` rows carries the scratch-row layout
    for a logical memory of `num_slots` rows."""
    return buf_rows == num_slots + SCRATCH_ROWS


def init_scratch_memory(batch: int, num_slots: int, word_size: int,
                        dtype=jnp.float32) -> jax.Array:
    """Zero-initialized (B, N+1, W) memory in the scratch-row layout.

    ``dtype`` is the *storage* dtype of the rows (``MemoryConfig.mem_dtype``
    / ``MemoryLayerConfig.mem_dtype``): bfloat16 halves the dominant state
    buffer, int8 quarters it (rows then carry a per-row f32 scale leaf —
    `init_scratch_mem_scale`); every read path upcasts/dequantizes gathered
    rows to float32 before the similarity/softmax math, so compute
    precision is unchanged."""
    return jnp.zeros((batch, num_slots + SCRATCH_ROWS, word_size),
                     dtype=dtype)


def init_scratch_mem_scale(batch: int, num_slots: int) -> jax.Array:
    """(B, N+1) f32 per-row dequantization scales for int8 memory storage
    (``mem_dtype="int8"``), in the scratch-row layout. All-zero rows carry
    scale 0.0 — the exact-zero invariant (`core/quant.py`): a cold slot
    dequantizes to exactly 0.0 with zero gradient. The scratch entry is
    pinned to 0.0 too, so the (never-read) scratch row dequantizes to
    zeros no matter what the write kernels park there."""
    from repro.core.quant import SCALE_DTYPE
    return jnp.zeros((batch, num_slots + SCRATCH_ROWS), SCALE_DTYPE)


def init_scratch_last_access(batch: int, num_slots: int) -> jax.Array:
    """(B, N+1) int32 usage table: the logical rows staggered with
    ``-arange(N)`` so the initial LRA ordering is well defined (slot N-1
    first), the scratch entry pinned to `LA_SCRATCH`. The single source of
    the scratch-row state init — SAM, SDNC, and the LM memory layer all
    build their usage tables here, and the checkpoint migration shim
    reproduces the same values."""
    return jnp.concatenate([
        jnp.broadcast_to(-jnp.arange(num_slots, dtype=jnp.int32)[None, :],
                         (batch, num_slots)),
        jnp.full((batch, SCRATCH_ROWS), LA_SCRATCH, jnp.int32)], axis=1)


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    """Configuration of the external memory (paper §3)."""

    num_slots: int = 1024          # N
    word_size: int = 32            # M (word size; `W` in code)
    num_heads: int = 4             # access heads (paper Suppl. C: 4)
    k: int = 4                     # K non-zero reads per head (paper: 4 or 8)
    delta: float = 0.005           # usage threshold δ (paper §3.2)
    # ANN backend: 'exact' (linear re-rank, still sparse-gradient) or 'lsh'.
    ann: str = "exact"
    # Kernel backend: 'ref' | 'pallas' | 'pallas-interpret' | a registered
    # custom name (repro.kernels.registry). None -> $REPRO_KERNEL_BACKEND
    # -> 'pallas' on a TPU, 'ref' elsewhere. Trace-time static; threaded
    # through every memory op.
    backend: Optional[str] = None
    # Storage dtype of the memory rows: 'float32' | 'bfloat16' | 'int8'.
    # Reads upcast gathered rows to float32 before the similarity/softmax
    # math, so bfloat16 halves the (B, N+1, W) buffer at unchanged compute
    # precision (writes round once per slot update). 'int8' quarters it:
    # rows store symmetric per-row quantized values with an f32 scale per
    # slot (`SAMState.mem_scale`), reads dequantize inside the fused
    # kernels, writes re-quantize the touched rows in the same pass, and
    # gradients follow the straight-through scheme in docs/memory-model.md
    # ("storage dtype ladder").
    mem_dtype: str = "float32"
    lsh_tables: int = 4
    lsh_bits: int = 8              # buckets per table = 2**bits
    lsh_bucket_size: int = 32
    # Dense-model (DAM/NTM/DNC) usage discount λ.
    usage_discount: float = 0.99

    @property
    def candidates(self) -> int:
        return self.lsh_tables * self.lsh_bucket_size


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    input_size: int = 8
    hidden_size: int = 100         # paper Suppl. C: 100 hidden units
    output_size: int = 8


class LSTMState(NamedTuple):
    h: jax.Array  # (B, H)
    c: jax.Array  # (B, H)


class ANNState(NamedTuple):
    """Fixed-shape LSH index state, partitioned by slot ownership
    (DESIGN.md §2, docs/sharding.md).

    Every bucket's ring is split into P ownership sub-rings: slot g lives in
    sub-ring ``g // (N / P)``, the same contiguous-block ownership rule the
    slot-sharded memory layout uses — so under a `mem_shard.memory_mesh`
    context with P == shards the partition dimension shards over the mesh
    axis and each device carries only the 1/P of the index covering the
    slots it owns. The canonical single-device index is the P=1 special
    case (one full-depth ring per bucket — the original layout).

    buckets: (B, T, n_buckets, P, d) int32 global slot-indices, -1 = empty;
             d = bucket_size // P (total per-bucket capacity is unchanged).
    cursor:  (B, T, n_buckets, P) int32 ring-insert position per sub-ring.
    """

    buckets: jax.Array
    cursor: jax.Array


class SparseRead(NamedTuple):
    """Result of a sparse content-based read."""

    indices: jax.Array   # (B, H, K) int32
    weights: jax.Array   # (B, H, K) float
    words: jax.Array     # (B, H, W) float — the read vectors r_t


class SAMState(NamedTuple):
    """SAM recurrent state. `memory`/`last_access` use the scratch-row layout
    (module docstring): row N is write scratch, never read, never LRA-picked.
    Legacy (B, N, W) states are still accepted by `sam_step` (detected by
    shape) so old checkpoints keep working through the migration shim."""

    memory: jax.Array        # (B, N+1, W) — row N = write scratch
    last_access: jax.Array   # (B, N+1) int32 — step of last access; [N]=LA_SCRATCH
    read: SparseRead         # previous step's read (for the write interpolation)
    ctrl: LSTMState
    step: jax.Array          # () int32
    ann: Optional[ANNState]  # None in 'exact' mode
    # Per-row f32 dequantization scales, (B, N+1) — only with int8 memory
    # storage (mem_dtype="int8"); None otherwise, which keeps the pytree
    # leaf set (and every existing checkpoint) unchanged for f32/bf16.
    mem_scale: Optional[jax.Array] = None


class DenseState(NamedTuple):
    """State for DAM / NTM (dense weightings)."""

    memory: jax.Array        # (B, N, W)
    usage: jax.Array         # (B, N) float — discounted usage (DAM) / unused (NTM)
    read_w: jax.Array        # (B, H, N) previous read weights
    read_words: jax.Array    # (B, H, W)
    write_w: jax.Array       # (B, H, N) previous write weights (NTM location addressing)
    ctrl: LSTMState
    step: jax.Array


class StepDeltas(NamedTuple):
    """Sparse modifications recorded by one SAM step — everything needed to
    roll the memory back *and* replay the step with fixed index selections
    during the backward pass (paper §3.4 / Suppl. Fig 5). This is SAM's
    delta type for the `MemoryCell` protocol (core/cell.py); the sparse DNC
    records the richer `SDNCDeltas` (core/dnc.py) covering its temporal
    link state as well."""

    write_idx: jax.Array     # (B, Hw) int32 rows touched by the write
    old_rows: jax.Array      # (B, Hw, W) their pre-write contents (raw
    #                          storage dtype: int8 rows record int8 bits,
    #                          so rollback is bit-exact)
    read_idx: jax.Array      # (B, H, K) int32 rows selected by the read,
    #                          *signed*: -1 = no valid candidate (cold LSH
    #                          index) — the replay reconstructs the zero-
    #                          weight validity mask from the sign
    # Pre-write per-row scales of the touched rows, (B, Hw) f32 — recorded
    # only under int8 memory storage (None otherwise) so rollback restores
    # the (row, scale) pair bit-exactly.
    old_scale: Optional[jax.Array] = None


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "size"))


def glorot(key, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[-2], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return (jax.random.normal(key, shape) * scale).astype(dtype)
