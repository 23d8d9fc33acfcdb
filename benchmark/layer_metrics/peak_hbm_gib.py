"""The peak the fullest chip held (``memory_stats()``: buffers in use
plus what loaded programs reserve for their temporaries), read when the
window has closed and before the reference runs."""


def read(trace, obs, cell, chip, say):
    peak = obs.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
