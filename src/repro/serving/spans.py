"""Names the serving path writes into a profiler trace, and the one
helper that opens a host span.

A host span is a `jax.profiler.TraceAnnotation`: the profiler keeps it
and writes it beside the device's own events, on their clock. With no
trace running, a span costs one Python object. Device operations carry
the `jax.named_scope` they were traced under in their op-name metadata
(`jit(decode)/while/body/attention/kv_write/...`).
"""
from __future__ import annotations

import jax

# host spans of ServeEngine.step, one per phase
STEP = "serve.step"          # the whole step
ADMIT = "serve.admit"        # pop_admissible, arena sizing, prefill-on-admit
PREFILL = "serve.prefill"    # one prompt-length group: prefill + KV writes
SAMPLE = "serve.sample"      # key split, take, sampling dispatch
PULL = "serve.pull"          # one device->host read: the host waits
DECODE = "serve.decode"      # feed/mask built, decode step dispatched
PRICE = "serve.price"        # the storage plane prices the step
FINISH = "serve.finish"      # tokens handed out, completions, slot release
SPANS = (STEP, ADMIT, PREFILL, SAMPLE, PULL, DECODE, PRICE, FINISH)

# named scopes of the traced decode, prefill and sampling code
SCOPES = ("attention", "kv_write", "ffn_hot", "predictor", "cold_layout",
          "cold_kernel", "experts", "lm_head", "sample", "prefill")

# StepResult.counts keys, plain ints per step; `price_keys` is the cold
# LRU keys the storage plane touched or admitted pricing the step (one
# per cluster while its accesses are whole clusters, else per neuron)
COUNTS = ("rows", "live", "prefill_groups", "prefill_tokens", "pulls",
          "built", "resized", "switched", "price_keys")

# span(name, **args): a host span, its args recorded with it
span = jax.profiler.TraceAnnotation
