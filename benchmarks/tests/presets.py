"""Tiny presets for the CPU: patches over the configuration and traffic
files, given to ``run.main(overrides=...)``. Shapes only: the code is the
cells' own."""
import io
import contextlib
import json

# limits: the cells' own are set from the chip's readings at full size; a
# net of 18 layers on 8 rows reads otherwise, so the preset brings its own
RESNET = {"config": {"num_layers": 18, "image_shape": [3, 72, 72],
                     "num_classes": 16,
                     "limits": {"loss_gap_step3": 0.2,
                                "grad_norm_gap_median": 0.05,
                                "delta_norm_gap_median": 0.05}},
          "traffic": {"batch_per_chip": 8, "warm_steps": 5}}

BLOOM = {"config": {"hidden_size": 64, "n_layer": 2, "n_head": 16,
                    "vocab_size": 512, "cache_len": 64, "slots": 4,
                    "max_new": 8, "check_pad_to": 40, "check_requests": 8,
                    "block_init_std": 0.2,
                    "env": {"MXTPU_SERVE_GENERATE_SLOTS": "4",
                            "MXTPU_SERVE_GENERATE_PREFILL_BUCKETS": "8,16,32",
                            "MXTPU_SERVE_GENERATE_MAX_NEW": "8"}},
         "traffic": {"clients": 8, "ramp_s": 0.3, "rate_per_s": 20,
                     "prompt_len": {"dist": "lognormal", "median": 12,
                                    "sigma": 0.8, "min": 4, "max": 30},
                     "output_len": {"dist": "lognormal", "median": 6,
                                    "sigma": 0.6, "min": 2, "max": 8}}}

# no cell sends an open loop yet (PERF.md section 7, the first open
# question): the generator's open loop is driven here over the saturated
# cell's configuration
OPEN = {"config": BLOOM["config"],
        "traffic": dict(BLOOM["traffic"], loop="open")}

PRESETS = {"resnet50-fit-synth": RESNET, "bloom1b7-saturated": BLOOM,
           "open-loop": OPEN}
CELL_OF = {"open-loop": "bloom1b7-saturated"}


def drive(preset, seed=11, seconds=1.0, stand_ins=()):
    """Everything of a run but the look for a chip; returns the parsed last
    line of standard output."""
    from benchmarks import run
    argv = ["--workload", CELL_OF.get(preset, preset), "--seed", str(seed),
            "--seconds", str(seconds), "--trace", "0"]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(argv, require_chip=False, overrides=PRESETS[preset],
                 stand_ins=stand_ins)
    return json.loads(buf.getvalue().strip().splitlines()[-1])
