"""Mean host time per step spent fetching the next batch from the program's
prefetcher and handing it to ``device_put`` (the benchmark's own span)."""


def read(run):
    waits = run.get("train", {}).get("data_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
