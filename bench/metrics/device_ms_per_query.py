"""Device: busy milliseconds in the traced window (decode, fused
evaluation, recompression and the rest) per request completed in it."""


def read(run):
    t = run["trace"]
    if t is None or not t["requests"]:
        return None
    return 1e3 * t["busy_s"] / t["requests"]
