"""Bytes an operation has to move, counted from shapes: what the algorithm
needs and no more, so a share of the memory roofline cannot read high."""

from __future__ import annotations


def ef_select_bytes(ef_numel: int, hbm_passes: int) -> int:
    """The fused error-feedback and select pass over one worker's flat
    gradient, `ef_numel` float32 values: 4 bytes an element for each of the
    three full-length arrays (residual read, gradient read, accumulator
    written) that the compiled step keeps in HBM.

    `hbm_passes` is read from the kernel's own HLO line in the trace of the
    run (`trace_reduce.hbm_passes`), not assumed. ISSUE 23 reckoned all
    three, 12 bytes, and on the chip that read 108 % of the HBM roofline for
    VGG-16 and 118 % for ResNet-50 (my chip runs, PR 23): the compiled step
    hands the kernel its gradient operand in another memory space (`S(1)`
    in the operand's layout), so two arrays cross HBM inside the kernel's
    time, 8 bytes. Should a later compile keep all three in HBM, the count
    follows. The candidates written beside the accumulator and the k pairs
    taken from them are left out (k is a thousandth of n)."""
    return 4 * ef_numel * hbm_passes
