"""A stand-in for a later PR's new kind of traffic: runs nothing, returns a
canned reading, so that the test can see the harness find it by name."""


def run(ctx):
    # no look for a chip, no JAX: the reading below is canned
    ctx.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ctx.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx.devices = []
    dev = {"plane": "/device:TPU:0", "steps": 2, "t0": 0.0, "t1": 4e6,
           "ops": [["a", 0.0, 1e6], ["b", 2e6, 4e6]], "busy_ns": 3e6,
           "span_ns": 4e6}
    return {"attempted": 2, "failed": 0, "memory_peak_bytes": 1,
            "end_to_end": {"setup_s": 0.1}, "correct": True,
            "compared": {"nothing": [0.0, 0.0]},
            "reading": {"summary": {"devices": [dev], "steps": 2,
                                    "span_s": 4e-3, "busy_s": 3e-3,
                                    "host": []},
                        "images_per_step": 2, "chips": 1,
                        "memory_peak_bytes": 1}}
