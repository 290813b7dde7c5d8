"""On-chip Pallas ring kernels for the sharded wave election (ISSUE 13).

The sharded wave solver's per-wave cross-shard traffic is a handful of
O(window) champion reductions (docs/SCALING.md §sharded-wave): exclusive
prefix sums of per-shard block aggregates (`ops.assign
.block_exclusive_offsets`), min-rank champion elections (`lax.pmin`), and
a packed admission-verdict `lax.psum`. On TPU each framework collective is
its own XLA program region with its own rendezvous; this module implements
the same exchanges as hand-rolled Pallas ring kernels — double-buffered
`pltpu.make_async_remote_copy` neighbor DMAs with send/recv semaphores in
scratch, local accumulation overlapped with the in-flight transfer — so a
wave's election costs one fused kernel launch per exchange point instead
of a framework collective, and the verdict `psum` disappears entirely
(the winning shard's node id and free row ride the election payload, so
every shard resolves the admission verdict replicated; see
`fused_election`).

Kernels
-------

- `ring_offsets` — (exclusive_prefix, total) of a per-shard value over the
  mesh axis: the (S-1)-step `lax.ppermute` exclusive scan rewritten as a
  neighbor-DMA ring. Exact-int64/float64 inputs travel as base-2^18 int32
  limbs (`split_limbs`/`join_limbs`): Mosaic has no f64/i64 vector units,
  and limb sums stay exact below 2^53 at any shard count <= 2^13, so the
  recombined prefix is BIT-IDENTICAL to the lax formulation's left-to-
  right float64 block sums.
- `elect_min` — elementwise global minimum of per-shard int32 candidate
  rows (the bucket-position election).
- `fused_election` — min-key champion election WITH winner payload: row 0
  is the rank key (min-reduced); the payload rows (winner node id, winner
  free-capacity limbs) are selected from whichever shard carried the
  winning key. Keys are globally unique by construction (every proposed
  rank lives in exactly one shard's block; the shared sentinel N carries a
  zero payload), so the select is order-independent and the reduction is
  exact.

Ring scheme (all three kernels share it)
----------------------------------------

Each shard owns a 3-slot VMEM communication buffer. Step k sends slot
(k-1)%3 to the RIGHT neighbor's slot k%3 via `make_async_remote_copy`
(send/recv DMA semaphores in scratch) and, while that transfer is in
flight, folds the buffer RECEIVED at step k-1 into the local accumulators
— the double-buffering overlap. After S-1 steps every shard has seen
every other shard's original contribution; prefix rows accumulate only
sources with ring index below their own (the exclusive scan), total/min/
select rows accumulate all. On real TPU a per-step neighbor barrier
(`pltpu.get_barrier_semaphore`, signal left+right / wait 2) bounds
neighbor skew to one step so a 3-slot buffer can never be overwritten
while its previous content is still being folded; the barrier primitive
has no CPU lowering, so the `interpret=True` CPU twin — which executes
shards serially and race-free — elides exactly those barrier ops and
nothing else. The twin is the differential-gate path: placements under
`SPT_PALLAS=1` must be bit-identical to the lax formulation
(tests/test_differential.py, `make pallas-smoke`).

VMEM envelope: one election program holds `1 + n_out + 3` same-shape
copies of its (H, L) int32 buffer (input, outputs, 3 comm slots) in VMEM
— worst family ring_offsets at 6 copies. The static envelope model lives
in `parallel.vmem` (shared with `tools/kernel_audit.py` KA001, which
re-derives it from the traced bodies); `PALLAS_MAX_ELECTION_ELEMS` is
derived there, no longer hand-picked. Call sites whose padded payload
exceeds it (the mega config's whole-queue first wave) statically keep
the lax collectives — bit-parity holds either way, and the tiled
large-window variant is on-chip follow-up work (docs/SCALING.md).

TPU gotchas honored (CLAUDE.md + /opt/skills/guides/pallas_guide.md): no
f64/i64 inside kernel bodies (limbs), buffers padded to (8, 128) int32
tiles, scalars never 0-D, static python loops only (shard count is a
static), and kernel bodies never read the clock or call back to the host
(tools/graft_lint.py GL011 enforces this at the source level).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from scheduler_plugins_tpu.parallel import vmem

__all__ = [
    "pallas_enabled",
    "pallas_interpret",
    "ring_offsets_i32",
    "ring_offsets_f64",
    "elect_min",
    "fused_election",
    "split_limbs",
    "join_limbs",
    "election_elems",
    "fits_election_budget",
    "PALLAS_MAX_ELECTION_ELEMS",
]

#: base-2^18 limb split for exact quantities (int64 in reference units,
#: cumulative sums documented < 2^53): 3 limbs cover 2^54, and per-limb
#: partial sums stay below 2^31 for any shard count <= 2^13 — no carry
#: propagation needed inside the ring, one normalize at recombine time
LIMB_BITS = 18
N_LIMBS = 3
_LIMB_MASK = (1 << LIMB_BITS) - 1

#: int32 sublane/lane tile floor for the padded kernel buffers
_SUBLANES = 8
_LANES = 128

#: ceiling on one election program's padded (H, L) int32 payload, DERIVED
#: from the static VMEM envelope model (`parallel.vmem`): the worst ring
#: family (ring_offsets: input + 2 outputs + 3 comm slots = 6 same-shape
#: buffers) must fit the per-core budget, so the gate is the largest
#: power of two with 6 * 4 B * E <= 16 MiB — 2^19. tools/kernel_audit.py
#: (KA001) re-derives the same number from the traced kernel bodies and
#: fails closed on drift. Oversize call sites (the mega whole-queue wave)
#: statically fall back to the lax collectives — same math, same
#: placements. SPT_PALLAS_MAX_ELECTION_ELEMS still overrides, inside
#: vmem.max_election_elems().
PALLAS_MAX_ELECTION_ELEMS = vmem.max_election_elems()

#: distinct collective_id per kernel family (kernels with custom barriers
#: must not share matching ids with unrelated collectives in the program)
_CID_OFFSETS = 11
_CID_ELECT_MIN = 12
_CID_FUSED = 13


def pallas_enabled() -> bool:
    """Opt-in gate for the Pallas election path (`SPT_PALLAS=1`). Read at
    solver BUILD time — callers key their trace caches on it (toggling the
    env var must never reuse a differently-built program), exactly like
    the SPT_SANITIZE discipline in `parallel.solver.profile_batch_fn`."""
    return os.environ.get("SPT_PALLAS", "") == "1"


def pallas_interpret() -> bool:
    """True when the kernels should run as their interpret-mode CPU twins:
    forced by `SPT_PALLAS_INTERPRET=0/1`, else everything except a real
    TPU backend interprets. The twin is the CI/differential path; the
    compiled kernels are what `tools/tpu_lower.py` AOT-lowers and what
    `chip_smoke.py --devices 4` runs on four chips."""
    forced = os.environ.get("SPT_PALLAS_INTERPRET")
    if forced is not None:
        return forced != "0"
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# limb packing (exact int64/float64 <-> int32 rows)
# ---------------------------------------------------------------------------


def split_limbs(x):
    """(N_LIMBS, ...) int32 base-2^18 limbs of a nonnegative exact-integer
    tensor (int64, or float64 holding integers < 2^53 — the repo-wide
    quantity bound). Lossless by construction; `join_limbs` inverts."""
    v = x.astype(jnp.int64) if x.dtype != jnp.int64 else x
    return jnp.stack(
        [
            ((v >> (LIMB_BITS * i)) & _LIMB_MASK).astype(jnp.int32)
            for i in range(N_LIMBS)
        ]
    )


@partial(jax.jit, static_argnames=("dtype",))
def join_limbs(limbs, dtype=jnp.float64):
    """Recombine `split_limbs` rows (possibly SUMMED across shards — each
    limb then holds up to shards * 2^18, still exact in f64) back into one
    tensor. float64 arithmetic is exact here: every limb < 2^31 and the
    recombined value < 2^53. A named jit boundary ON PURPOSE (XLA inlines
    it — no runtime cost): the exactness argument is structural (the
    recombined value IS the original < 2^53 quantity sum), so
    `tools/kernel_audit.py` KA003 blesses the jit call by name via
    `api.bounds.EXACT_FN_BOUNDS` — the naive interval on `limb2 * 2^36`
    overflows the 2^53 line that the reconstructed value respects."""
    acc = limbs[0].astype(jnp.float64)
    for i in range(1, N_LIMBS):
        acc = acc + limbs[i].astype(jnp.float64) * float(1 << (LIMB_BITS * i))
    return acc.astype(dtype)


def _pad2(x, fill):
    """Pad a 2-D int32 buffer up to the (8, 128) tile floor."""
    H, L = x.shape
    Hp = -(-H // _SUBLANES) * _SUBLANES
    Lp = -(-L // _LANES) * _LANES
    if Hp == H and Lp == L:
        return x
    return jnp.pad(x, ((0, Hp - H), (0, Lp - L)), constant_values=fill)


def election_elems(n_rows: int, length: int) -> int:
    """Padded int32 element count of one (n_rows, length) kernel buffer —
    the quantity `PALLAS_MAX_ELECTION_ELEMS` bounds."""
    Hp = -(-n_rows // _SUBLANES) * _SUBLANES
    Lp = -(-length // _LANES) * _LANES
    return Hp * Lp


def fits_election_budget(n_rows: int, length: int) -> bool:
    return election_elems(n_rows, length) <= PALLAS_MAX_ELECTION_ELEMS


# ---------------------------------------------------------------------------
# the shared ring engine
# ---------------------------------------------------------------------------


def _ring_kernel_body(x_ref, out_refs, comm, send_sem, recv_sem, *,
                      axis_name: str, n_shards: int, interpret: bool,
                      init_fn, combine_fn, finish_fn):
    """One (S-1)-step double-buffered neighbor-DMA ring. `init_fn(x)`
    builds the accumulator pytree from the local contribution;
    `combine_fn(acc, recv, src_offset)` folds the buffer received from the
    shard `src_offset` ring positions to the left; `finish_fn(acc,
    out_refs)` writes the results. The per-step barrier (TPU only; the
    interpret twin is serially executed and race-free) bounds neighbor
    skew so the 3-slot buffer is never overwritten before its previous
    content has been folded."""
    import numpy as np

    my = jax.lax.axis_index(axis_name)
    S = jnp.int32(n_shards)
    right = jax.lax.rem(my + jnp.int32(1), S)
    left = jax.lax.rem(my + S - jnp.int32(1), S)
    comm[np.int32(0)] = x_ref[...]
    acc = init_fn(x_ref[...])
    if not interpret:
        barrier = pltpu.get_barrier_semaphore()
    for k in range(1, n_shards):
        # np.int32 slot indices: python-int literals promote to i64 under
        # x64, which Mosaic's memref_slice rejects
        slot, nxt = np.int32((k - 1) % 3), np.int32(k % 3)
        if not interpret:
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=left,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            pltpu.semaphore_wait(barrier, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm.at[slot],
            dst_ref=comm.at[nxt],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[nxt],
            device_id=right,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        # overlap: fold the buffer received at step k-1 (the value of the
        # shard k-1 positions left) while step k's transfer is in flight
        if k >= 2:
            acc = combine_fn(acc, comm[slot], k - 1)
        rdma.wait()
    acc = combine_fn(
        acc, comm[np.int32((n_shards - 1) % 3)], n_shards - 1
    )
    finish_fn(acc, out_refs)


def _ring_call(x2d, axis_name: str, n_shards: int, interpret: bool,
               n_out: int, collective_id: int, init_fn, combine_fn,
               finish_fn, pad_fill: int = 0, padded=None,
               family: str = "ring"):
    """`pl.pallas_call` plumbing shared by ALL the kernels: pads the
    (H, L) int32 buffer to the tile floor (`pad_fill` — 0 for sum/prefix
    rows, INT32_MAX for min keys; `padded` lets a caller supply a buffer
    with MIXED fills, fused_election's key row vs payload rows),
    allocates the 3-slot comm scratch and DMA semaphores, and returns the
    UNPADDED outputs. One copy on purpose: the scratch/semaphore layout
    must never diverge between kernels."""
    H, L = x2d.shape

    def kernel(x_ref, *refs):
        out_refs = refs[:n_out]
        comm, send_sem, recv_sem = refs[n_out:]
        _ring_kernel_body(
            x_ref, out_refs, comm, send_sem, recv_sem,
            axis_name=axis_name, n_shards=n_shards, interpret=interpret,
            init_fn=init_fn, combine_fn=combine_fn, finish_fn=finish_fn,
        )

    if padded is None:
        padded = _pad2(x2d, pad_fill)
    Hp, Lp = padded.shape
    out = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct((Hp, Lp), jnp.int32) for _ in range(n_out)
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=tuple(
            pl.BlockSpec(memory_space=pltpu.VMEM) for _ in range(n_out)
        ),
        scratch_shapes=[
            pltpu.VMEM((3, Hp, Lp), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id
        ),
        interpret=interpret,
        # the family name rides the traced pallas_call so the kernel
        # auditor's per-family envelope cross-check (vmem.RING_FAMILIES)
        # can key traced bodies back to the budget table
        name=family,
    )(padded)
    return tuple(o[:H, :L] for o in out)


# ---------------------------------------------------------------------------
# public kernels
# ---------------------------------------------------------------------------


def _offsets_rows(rows, axis_name, n_shards, interpret):
    """(exclusive_prefix, total) of int32 `rows` (H, L) over the mesh axis
    — the ring engine with prefix/total accumulators. Padding rows are
    zero, so they sum to zero and never perturb the real rows."""

    def init(x):
        return {"excl": jnp.zeros_like(x), "tot": x}

    def combine(acc, recv, src_off):
        my = jax.lax.axis_index(axis_name)
        # the shard src_off ring positions to the LEFT contributed `recv`;
        # its ring index is my - src_off, i.e. strictly below mine (the
        # exclusive-prefix condition) exactly when src_off <= my
        take = (src_off <= my).astype(jnp.int32)
        return {
            "excl": acc["excl"] + recv * take,
            "tot": acc["tot"] + recv,
        }

    def finish(acc, out_refs):
        out_refs[0][...] = acc["excl"]
        out_refs[1][...] = acc["tot"]

    return _ring_call(
        rows, axis_name, n_shards, interpret, 2, _CID_OFFSETS,
        init, combine, finish, family="ring_offsets",
    )


def ring_offsets_i32(x, axis_name: str, n_shards: int, *, interpret: bool):
    """(exclusive_prefix, total) of a per-shard int32 value `x` (any
    shape) — the Pallas twin of `ops.assign.block_exclusive_offsets` for
    int32 payloads (rescue feasible counts). Caller contract: totals fit
    int32 (counts are bounded by the padded node count). Bit-identical to
    the lax formulation: integer addition is exact in any order."""
    if n_shards == 1:
        return jnp.zeros_like(x), x
    flat = x.reshape(1, -1).astype(jnp.int32)
    excl, tot = _offsets_rows(flat, axis_name, n_shards, interpret)
    return excl.reshape(x.shape), tot.reshape(x.shape)


def ring_offsets_f64(x, axis_name: str, n_shards: int, *, interpret: bool):
    """(exclusive_prefix, total) of a per-shard float64 exact-integer
    value `x` (the cumulative-free block aggregates): base-2^18 limbs ride
    the int32 ring and recombine exactly, so the result is bit-identical
    to the lax float64 block sums below the documented 2^53 bound."""
    if n_shards == 1:
        return jnp.zeros_like(x), x
    limbs = split_limbs(x)  # (N_LIMBS, ...)
    rows = limbs.reshape(N_LIMBS, -1)
    excl, tot = _offsets_rows(rows, axis_name, n_shards, interpret)
    shape = (N_LIMBS,) + x.shape
    return (
        join_limbs(excl.reshape(shape)),
        join_limbs(tot.reshape(shape)),
    )


def elect_min(rows, axis_name: str, n_shards: int, *, interpret: bool):
    """Elementwise global MINIMUM of per-shard int32 `rows` (H, L) — the
    bucket-position champion election (`lax.pmin` twin). Padding lanes
    are filled with INT32_MAX so they never win."""
    if n_shards == 1:
        return rows

    def init(x):
        return x

    def combine(acc, recv, _src_off):
        return jnp.minimum(acc, recv)

    def finish(acc, out_refs):
        out_refs[0][...] = acc

    (out,) = _ring_call(
        rows.astype(jnp.int32), axis_name, n_shards, interpret, 1,
        _CID_ELECT_MIN, init, combine, finish,
        pad_fill=jnp.iinfo(jnp.int32).max, family="elect_min",
    )
    return out


def fused_election(keys, payload_rows, axis_name: str, n_shards: int, *,
                   interpret: bool):
    """Min-key champion election WITH winner payload, in ONE ring program:
    `keys` (L,) int32 are per-shard candidate ranks (the shared sentinel
    for "no candidate" may repeat; real keys are globally unique — every
    proposed rank lives in exactly one shard's block); `payload_rows`
    (Hp, L) int32 are that shard's attachment (winner node id, free-row
    limbs). Returns (min_keys (L,), winner_payload (Hp, L)).

    This is the kernel that retires the packed admission-verdict `psum`:
    because the winner's free row arrives with the election result, the
    queue-order admission check runs REPLICATED on every shard instead of
    sharded-then-psum'd (`ops.assign.waterfill_targeted_sharded`'s pallas
    path), so the wave's champion reduction and verdict resolution cost
    one fused collective program. Sentinel keys tie with payload zero on
    every shard, so keeping the accumulator on ties is exact."""
    if n_shards == 1:
        return keys, payload_rows
    L = keys.shape[0]
    buf = jnp.concatenate(
        [keys.reshape(1, L).astype(jnp.int32),
         payload_rows.astype(jnp.int32)], axis=0
    )

    def init(x):
        return x

    def combine(acc, recv, _src_off):
        take = recv[0:1] < acc[0:1]  # (1, L) strict: keys unique or tied-0
        key = jnp.minimum(acc[0:1], recv[0:1])
        rest = jnp.where(take, recv[1:], acc[1:])
        return jnp.concatenate([key, rest], axis=0)

    def finish(acc, out_refs):
        out_refs[0][...] = acc

    # key padding lanes carry INT32_MAX (never win); payload pad rows are
    # zero — pad by hand so the two fills coexist in one buffer
    H = buf.shape[0]
    padded = _pad2(buf, 0).at[0, L:].set(jnp.iinfo(jnp.int32).max)
    (out,) = _ring_call(
        buf, axis_name, n_shards, interpret, 1, _CID_FUSED,
        init, combine, finish, padded=padded, family="fused_election",
    )
    return out[0], out[1:H]
