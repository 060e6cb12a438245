"""Mean host time per step of the window spent inside the data source's
``batch_at`` (the program's prefetcher handing over the next batch)."""


def read(view):
    if not view.data_wait_s:
        return None
    return 1e3 * sum(view.data_wait_s) / len(view.data_wait_s)
