"""Benchmark harness — one module per paper table/figure + framework
selection benches. Entry: python -m benchmarks.run"""
