"""The least work ``hbam_seq_stats_kernel``'s result needs, counted from the
sizes the runner reports under ``observations["reads"]`` (records reduced in
the window, bases a read) and from nothing the program says about itself: the
padding of its strides is not work the result needs, so no implementation can
read over 100 % of the roofline and a leaner one reads higher.
``benchmark/reducers/roofline_reads.py`` divides by the device seconds of the
named ops.  (``benchmark/kernel_work.py`` holds the GWAS kernels' twins; a
file the benchmark has is not edited.)

Returns ``(operations, bytes)`` of ALL the records of the window."""
from __future__ import annotations


def seq_stats(sizes: dict):
    """A record's bases packed two a byte, its qualities a byte each and its
    int32 length, each read once from HBM; four operations a base (the
    nibble's unpack, the G | C test, the quality's add, the histogram's
    add).  Memory binds: 156 B against 404 operations a 101-base record is
    0.19 ns at the HBM peak against 0.002 ns at the bf16 peak."""
    n, length = int(sizes["records"]), int(sizes["read_len"])
    return 4 * n * length, n * ((length + 1) // 2 + length + 4)


KERNELS = {"seq_stats": seq_stats}
