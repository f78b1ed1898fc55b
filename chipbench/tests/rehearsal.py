"""The smollm cell cut to CPU size, for the rehearsal tests: the
harness's whole run after the look for a chip, on the program as it
is, with the Pallas kernel in interpret mode.

It is served in float32, so that a sound run reads rounding alone and
each fault stands far above it: in bfloat16 at this size the pick gap
of sound runs (up to 0.021) came within twice the control's (0.042).
The cell compares the numbers its chip cell compares, with limits for
this size set between the largest reading of sound runs and the
smallest of the control (the reference on float8 weights), over six
seeds and more (on the CPU, seven seeds):

* token_gap_mean: sound 0 on every seed, control >= 0.0092: limit
  0.001;
* pick_gap_p99: sound 0 on every seed, control >= 0.124: limit 0.01.
"""
import copy

from chipbench import spec
from chipbench.run import run_cell


def config() -> dict:
    c = copy.deepcopy(spec.load_json(spec.HERE / "configs"
                                     / "smollm-135m.json"))
    c.update(hidden_size=128, intermediate_size=512, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    c["serve"].update(predictor_rank=16, dtype="float32")
    return c


CLOSED = {"loop": "closed", "clients": 4, "pool": 512, "block": 8,
          "prompt": {"median": 24, "sigma": 0.5, "lengths": [16, 32]},
          "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
          "engine": {"ctx_budget": 48, "buckets": [1, 2, 4]}}
OPEN = dict(CLOSED, loop="open", pool=64,
            arrivals={"process": "gamma", "cv": 3.0, "rate_per_s": 40.0})

LIMITS = {"token_gap_mean": 0.001, "pick_gap_p99": 0.01}


def cell(mix=CLOSED) -> spec.Cell:
    real = spec.load_cell("smollm-135m.chat-c8")
    limits = {"limits": {k: LIMITS[k] for k in real.limits["limits"]}}
    return spec.Cell("cpu-smollm", 1, config(), mix, limits,
                     real.end_to_end, real.per_layer)


def run(seed: int = 2**31 + 5, mix=CLOSED, seconds=0.6, **kw) -> dict:
    return run_cell(cell(mix), seed, seconds, False, {}, use_cache=False,
                    **kw)
