"""Mean rows per decode step over the window: tokens the fused ticks
emitted over the device steps they ran (``EngineStats`` counters)."""


def read(run):
    c = run.window.counters
    if not c["device_steps"]:
        return None
    return c["tokens_decoded"] / c["device_steps"]
