"""peak_hbm_gib: ``peak_bytes_in_use`` of the fullest chip after the window,
in GiB, as the runtime counts it."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
