"""Pallas TPU kernel for capacity-aware greedy assignment.

Same semantics as ops.select.greedy_assign's lax.scan — including bitwise-
identical tie-break noise (select.tie_noise's murmur3 finalizer) — but the
sequential-by-construction pod loop runs as a pallas grid on the TensorCore
with the free-capacity matrix resident in VMEM:

  * grid = (P/8,): TPU grid steps execute sequentially on the core, and
    each step walks POD_BLOCK=8 pods with an in-kernel fori_loop. Blocks
    of 8 rows satisfy the Mosaic tiling rule that a block's second-to-
    last dim be a multiple of 8 (a (1, N) per-pod block does NOT lower —
    the round-1 kernel failed exactly there on real hardware).
  * the running free matrix lives in the freeout output block (constant
    index map → one persistent VMEM buffer across grid steps; the
    standard accumulator pattern), stored transposed (R, N): R resource
    rows (9 axes) on sublanes x N node lanes, so the per-pod "fits" check
    is an R-row AND-reduce onto (1, N) and the capacity update is a
    lane-masked FMA — no dynamic-lane scatter.
  * each pod's request row loads from the step's (8, R) request block
    with a dynamic SUBLANE slice, then reshapes (1, R) → (R, 1) to meet
    the transposed free matrix (both verified to lower; dynamic LANE
    slicing and lax.dynamic_slice on values do not lower on this
    toolchain, and a one-hot matmul through the MXU could round values
    via its f32 decomposition).
  * each step's (8, N) score block streams HBM→VMEM via the pallas
    pipeline (double-buffered by the runtime); total HBM traffic ≈ the
    score matrix once (~P·N·4 bytes), vs the scan path re-materializing
    mask/argmax intermediates through HBM each step.

Measured on one v5e core (P=10240, N=50176, R=9): 87 ms vs 981 ms for the
lax.scan path — 11.3x, bitwise-identical outputs. CPU tests run it under
interpret=True for exact equivalence checks against the scan
(tests/test_pallas_select.py); chip_smoke.py (phase 4) and bench.py
assert the same equality on the chip, and tests/test_tpu_compile.py
compiles it for a described v5e at this shape.

SHORTLIST GATE: with shortlist-compressed arbitration on (the default,
MINISCHED_SHORTLIST=1), build_step does NOT auto-select this kernel —
the K-wide certified scan (ops/select.greedy_assign_shortlist) replaces
it as the sequential stage, since both attack the same critical path and
the shortlist's per-step argmax is ~N/K narrower than this kernel's
full-width one. The gate is counted, not silent: the engine's
``shortlist_width`` gauge > 0 says the scan ran compressed, 0 says this
kernel (or the full scan) handled the batch. Mirroring the shortlist
INSIDE the kernel needs a dynamic-lane gather per step (free[cand_ids]),
which Mosaic does not lower on this toolchain (same class as the
dynamic LANE slicing noted above) — re-evaluate when it does. An
explicit ``pallas=True`` (bench.py's kernel-vs-scan comparison) still
selects the kernel unconditionally.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .select import AssignResult, seed_from_key, tie_noise_from_cols

POD_BLOCK = 8   # pods per grid step == the f32 sublane tile height
LANE_TILE = 128  # node-axis pad quantum == the f32 lane tile width


def _kernel(scores_ref, req_ref, free0_ref, seed_ref,
            chosen_ref, ok_ref, freeout_ref):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        freeout_ref[:] = free0_ref[:]

    neg = jnp.float32(-3.0e38)  # == select.NEG; literal so the kernel
    B = POD_BLOCK
    N = scores_ref.shape[1]
    R = req_ref.shape[1]
    seed = seed_ref[0, 0]
    col = jax.lax.broadcasted_iota(jnp.uint32, (1, N), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)

    def body(j, carry):
        # The running free matrix lives in freeout_ref and is updated IN
        # PLACE — carrying it as a loop value doubles the (R, N) VMEM
        # footprint, which blows the scoped-VMEM budget at N=50k.
        chosen_acc, ok_acc = carry
        i = g * B + j                                      # global pod row
        req = req_ref[pl.ds(j, 1), :].reshape(R, 1)
        srow = scores_ref[pl.ds(j, 1), :]                  # (1, N)
        free = freeout_ref[:]
        fits = jnp.all(free >= req, axis=0, keepdims=True)  # (1, N)
        s = jnp.where(fits, srow, neg)
        m = jnp.max(s)
        ok = m > neg

        # Tie-break noise: the same definition the scan path uses (2D iota
        # — TPU has no 1D iota), so both paths pick identical nodes.
        noise = tie_noise_from_cols(seed, i, col)
        tie = (s >= m) & fits
        idx = jnp.argmax(jnp.where(tie, noise, -1.0)).astype(jnp.int32)

        # Lane-masked capacity update (no dynamic-lane scatter): subtract
        # req from exactly the chosen column, or nothing when no node fit.
        take = ((col == idx.astype(jnp.uint32)) & ok).astype(jnp.float32)
        freeout_ref[:] = free - req * take

        at_j = rows == j
        chosen_acc = jnp.where(at_j, jnp.where(ok, idx, -1), chosen_acc)
        ok_acc = jnp.where(at_j, ok.astype(jnp.int32), ok_acc)
        return chosen_acc, ok_acc

    chosen_acc, ok_acc = jax.lax.fori_loop(
        0, B, body,
        (jnp.full((B, 1), -1, jnp.int32),
         jnp.zeros((B, 1), jnp.int32)))
    chosen_ref[:] = chosen_acc
    ok_ref[:] = ok_acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def greedy_assign_pallas(scores: jnp.ndarray, requests: jnp.ndarray,
                         free0: jnp.ndarray, key: jax.Array,
                         *, interpret: bool = False) -> AssignResult:
    """Drop-in replacement for select.greedy_assign on TPU.

    scores:   (P,N) f32 with NEG on infeasible pairs (priority row order)
    requests: (P,R) f32 per-pod resource requests
    free0:    (N,R) f32 free resources entering the batch
    """
    P, N = scores.shape
    R = requests.shape[1]
    if P % POD_BLOCK:
        # Pad to the block height; padded rows score NEG everywhere →
        # never assigned, never consume capacity. Sliced off below.
        pad = POD_BLOCK - P % POD_BLOCK
        scores = jnp.pad(scores, ((0, pad), (0, 0)),
                         constant_values=-3.0e38)  # == select.NEG in f32
        requests = jnp.pad(requests, ((0, pad), (0, 0)))
    free_t = free0.T            # (R, N): resources on sublanes, nodes on lanes
    if N % LANE_TILE:
        # Pad the node axis to the lane tile so EVERY node count runs the
        # kernel (off-tile N used to fall back to the 2-11x slower scan).
        # Pad columns score NEG → never in the argmax tie set, never
        # chosen, never debit capacity; chosen indices stay < N.
        pad_n = LANE_TILE - N % LANE_TILE
        scores = jnp.pad(scores, ((0, 0), (0, pad_n)),
                         constant_values=-3.0e38)
        free_t = jnp.pad(free_t, ((0, 0), (0, pad_n)))
    P_pad, N_pad = scores.shape
    seed = seed_from_key(key).reshape(1, 1)

    chosen, ok, free_t_after = pl.pallas_call(
        _kernel,
        grid=(P_pad // POD_BLOCK,),
        in_specs=[
            pl.BlockSpec((POD_BLOCK, N_pad), lambda g: (g, 0)),  # scores
            pl.BlockSpec((POD_BLOCK, R), lambda g: (g, 0)),  # request rows
            pl.BlockSpec((R, N_pad), lambda g: (0, 0)),      # initial free
            pl.BlockSpec(memory_space=pltpu.SMEM),           # tie-break seed
        ],
        out_specs=[
            pl.BlockSpec((POD_BLOCK, 1), lambda g: (g, 0)),
            pl.BlockSpec((POD_BLOCK, 1), lambda g: (g, 0)),
            pl.BlockSpec((R, N_pad), lambda g: (0, 0)),  # free accumulator
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((P_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((R, N_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # scores block (double-buffered) + free0 + the free accumulator
            # legitimately near the default 16 MB scoped-VMEM cap at
            # N=50k; v5e has headroom above it.
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )(scores, requests, free_t, seed)

    return AssignResult(chosen=chosen[:P, 0],
                        assigned=ok[:P, 0].astype(bool),
                        free_after=free_t_after[:, :N].T)


def greedy_assign_kernel(scores: jnp.ndarray, requests: jnp.ndarray,
                         free0: jnp.ndarray, key: jax.Array) -> AssignResult:
    """greedy_assign_pallas for the platform the step is lowered for: the
    Mosaic kernel on TPU, the same kernel interpreted anywhere else (the
    CPU rehearsal of an explicit ``pallas=True`` step). Chosen at
    lowering, so a step compiled for a described TPU holds the kernel."""
    return jax.lax.platform_dependent(
        scores, requests, free0, key, tpu=greedy_assign_pallas,
        default=functools.partial(greedy_assign_pallas, interpret=True))


def pallas_supported(n_nodes: int, backend: str | None = None) -> bool:
    """True when the kernel path is available — any node count on TPU:
    both axes self-pad inside greedy_assign_pallas (pods to POD_BLOCK,
    nodes to LANE_TILE with NEG-scored pad columns), so off-tile shapes
    no longer fall back to the lax.scan path."""
    if backend is None:
        backend = jax.default_backend()
    return backend == "tpu" and n_nodes >= 1
