"""The client's median time to first token, for cells where it is not an
end-to-end metric: where a step is long beside the median and a window holds
few requests (``mistral7b-chat-open``: steps of 158 ms, a median near 1 s over
28 requests), the median swings by a step's length from run to run, wider
than half of the largest bound allowed, so it is read here and not judged."""
from benchmarks.harness.stats import end_to_end

LAYER = "load generator"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p95_ms"


def read(run):
    return end_to_end(run["records"], loop=run["loop"], t0=run["t0"],
                      window_s=run["window_s"]).get("ttft_p50_ms")
