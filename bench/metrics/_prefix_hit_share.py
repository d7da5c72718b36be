"""Scheduler and prefix index: the share of prompt tokens admitted in the
window whose KV came from the prefix cache (the engine's
``shared_tokens / prefill_tokens`` counters, differenced over the
window), in percent."""


def read(run):
    c = run["counters"]
    if not c["prefill_tokens"]:
        return None
    return 100.0 * c["shared_tokens"] / c["prefill_tokens"]
