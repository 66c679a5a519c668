"""Peak device memory after the window, on the fullest chip, in GiB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2 ** 30
