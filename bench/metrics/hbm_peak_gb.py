"""Peak device memory of the compiled train step, as the compiler counts
it (``memory_analysis().peak_memory_in_bytes``), in GB."""


def read(view):
    if not view.hbm_peak_bytes:
        return None
    return view.hbm_peak_bytes / 1e9
