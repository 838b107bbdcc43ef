"""Device kernels of the dynamic-loop-fusion system (DESIGN.md §2, §8):
the wave executor's gather→scatter step loop (``wave_exec``), the
frontier merge (``du_hazard``), fused producer/consumer streams
(``fused_stream``), the workload kernels (``csr_spmv``, ``histogram``)
and the numpy oracles of the speculative kernels (``dynloop``). Each
Pallas kernel has kernel.py / ops.py / ref.py."""
