"""Layer: device. `device.memory_peak_bytes` of the result line in GiB: NOT
the allocator's reading. On this runtime `memory_stats()["peak_bytes_in_use"]`
counts the arrays that live on the device and not a running program's
temporaries (0.73 GiB beside 4.17 GiB of them for VGG-16), so the number is
the bytes at rest after the window (`bytes_in_use`: both trainers' state and
the batches in flight) plus the larger step program's temporaries and fresh
outputs by XLA's `memory_analysis()` of the compiled program, and never less
than the allocator's peak. `harness.say_memory` prints all three. Moves
`examples_per_s` (a change that buys speed with memory shows here).
Source: program_counter (the allocator's bytes at rest and the compiler's
account of the step program, not a reading of the peak)."""


def read(run):
    return run["device"]["memory_peak_bytes"] / float(1 << 30)
