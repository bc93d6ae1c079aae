"""Share, in percent, of the memory roofline the decode step reaches: the
least bytes a step must move (bf16 weights once + the K/V of the tokens
resident in the live sequences, from the benchmark's shape function) over
the chip's published HBM bandwidth, divided by the device time of one
decode program in the trace. The bound is memory, not compute."""


def read(rec):
    trace = rec.get("trace")
    if rec.get("kind") != "serve" or not trace or not rec.get("peaks") \
            or trace.get("decode_min_bytes") is None:
        return None
    steps = [m for name, m in trace.get("modules", {}).items()
             if "decode" in name]
    if not steps:
        return None
    step_s = max(steps, key=lambda m: m["count"])["median_s"]
    least_s = trace["decode_min_bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
