"""Benchmark harness (deliverable d): one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig7 fig14 # filter by tag
"""
import sys
import time
import traceback

MODULES = [
    ("fig2_sparsity", "benchmarks.bench_sparsity"),
    ("fig6_pipeline", "benchmarks.bench_pipeline"),
    ("fig7_decode", "benchmarks.bench_decode"),
    ("fig8_prefill", "benchmarks.bench_prefill"),
    ("fig10_memory", "benchmarks.bench_memory"),
    ("table5_latency", "benchmarks.bench_latency"),
    ("fig13_bon", "benchmarks.bench_bon"),
    ("serving_stream", "benchmarks.bench_serving"),
    ("fig14_ablation", "benchmarks.bench_ablation"),
    ("table4_io_split", "benchmarks.bench_io_split"),
    ("table7_accuracy", "benchmarks.bench_accuracy"),
    ("kernels", "benchmarks.bench_kernels"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failures = 0
    for tag, modname in MODULES:
        if filters and not any(f in tag for f in filters):
            continue
        t0 = time.time()
        print(f"# --- {tag} ({modname}) ---", flush=True)
        try:
            mod = __import__(modname, fromlist=["main"])
            mod.main()
            print(f"# {tag} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {tag} FAILED:\n{traceback.format_exc()}", flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == '__main__':
    main()
