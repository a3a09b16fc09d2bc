"""Machine models: Manticore (the paper's target) and TPU v5e (ours).

The paper's space-complexity arguments (Sections 2.1.2, 2.2.2, 2.3.2, 3.1.2,
3.2.2) are all of the form "working set + DMA double-buffers must fit the
128 KiB cluster scratchpad".  We encode that capacity argument once, here,
parameterized by the machine, so the *same* chooser that reproduces the
paper's Manticore numbers (Delta_O <= 24/12/23/11, D_O <= 768/384) also picks
Pallas BlockSpec block sizes against TPU VMEM.
"""

from __future__ import annotations

import dataclasses

KIB = 1024
MIB = 1024 * 1024


@dataclasses.dataclass(frozen=True)
class MachineModel:
    """Capacity/bandwidth model of one compute unit and its fabric."""

    name: str
    # Fast local memory per compute unit (Manticore: L1 SPM; TPU: VMEM).
    local_mem_bytes: int
    # Bytes reserved per DMA stream to cover main-memory round-trip latency
    # (paper Sec. 2.1.2: 256 cycles x 64 B/cycle = 16 KiB per stream).
    dma_buffer_bytes: int
    # Compute units that can share data over the fast local network
    # (paper: 16 clusters per L2 quadrant; TPU: chips on an ICI ring axis).
    local_group_size: int
    # Peak compute, main-memory BW, and local-link BW (for rooflines).
    peak_flops: float
    main_mem_bw: float
    link_bw: float
    # Number of compute units in one "chip" (Manticore chiplet: 128 clusters).
    units: int = 1
    # Block-size granularity the compute unit wants (TPU MXU/VPU lane width:
    # 128; Manticore clusters have no alignment constraint: 1).  Planners in
    # repro.plan emit blocks in multiples of this.
    lane: int = 1
    # Whether streamed input blocks are double-buffered *inside* the local
    # memory budget (Pallas holds whole blocks in VMEM: True) or flow through
    # the fixed reserved DMA buffers (Manticore's 16 KiB stream buffers,
    # paper Sec. 2.1.2: False — only the working set is charged).
    charge_stream_blocks: bool = True

    def dma_reserve(self, streams: int) -> int:
        """Bytes reserved for ``streams`` double-buffered DMA streams."""
        return streams * self.dma_buffer_bytes

    def usable_for_working_set(self, streams: int) -> int:
        return self.local_mem_bytes - self.dma_reserve(streams)


# The paper's machine (Sec. 1): 128 KiB L1 per cluster, 16 KiB per DMA
# stream buffer, 16 clusters per L2 quadrant, 8 FPUs x 1 dp-MAC/cycle
# (2 sp-MACs/cycle) @ 1 GHz nominal, 512-bit DMA @ 1 GHz into the tree NoC.
MANTICORE = MachineModel(
    name="manticore",
    local_mem_bytes=128 * KIB,
    dma_buffer_bytes=16 * KIB,
    local_group_size=16,
    peak_flops=128 * 8 * 2 * 2 * 1e9,  # chiplet, sp: 128 cl x 8 FPU x 2 MAC x 2 flop
    main_mem_bw=64 * 1e9,  # one 512-bit HBM2E port @ 1 GHz
    link_bw=64 * 1e9,  # 512-bit cluster DMA port @ 1 GHz
    units=128,
    lane=1,
    charge_stream_blocks=False,  # streams ride the reserved 16 KiB buffers
)

# TPU v5e (the adaptation target; constants fixed by the assignment):
# 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s per ICI link.  VMEM is ~128 MiB
# on v5e-class chips but a Pallas kernel should budget well under that; we
# model 64 MiB usable and 4 MiB per double-buffered pipeline stream.
TPU_V5E = MachineModel(
    name="tpu_v5e",
    local_mem_bytes=64 * MIB,
    dma_buffer_bytes=4 * MIB,
    local_group_size=16,  # one axis of a 16x16 pod slice
    peak_flops=197e12,
    main_mem_bw=819e9,
    link_bw=50e9,
    units=1,
    lane=128,
    charge_stream_blocks=True,  # Pallas double-buffers whole blocks in VMEM
)

# The same chip as seen by a kernel that passes the compiler no VMEM limit
# (the grouped matmul of jax's megablox): it compiles under the compiler's
# default scoped limit, 16 MiB on v5e, and its planner counts every block
# of it, double buffers included, so no stream reservation is set apart.
TPU_V5E_SCOPED = dataclasses.replace(
    TPU_V5E, name="tpu_v5e_scoped", local_mem_bytes=16 * MIB,
    dma_buffer_bytes=0)

MACHINES = {m.name: m for m in (MANTICORE, TPU_V5E, TPU_V5E_SCOPED)}


def machine_named(name: str, default: MachineModel = TPU_V5E) -> MachineModel:
    """The registered MachineModel for a Schedule's ``machine`` name
    (falls back to ``default`` for unregistered names)."""
    return MACHINES.get(name, default)


WORD_BYTES = {"sp": 4, "dp": 8, "bf16": 2, "f32": 4, "f64": 8}


def word_bytes(precision: str) -> int:
    try:
        return WORD_BYTES[precision]
    except KeyError:
        raise ValueError(f"unknown precision {precision!r}") from None
