"""The fullest chip's peak of bytes in use plus bytes reserved
(`run.py: device_report`), in GiB."""


def read(run):
    if not run.memory_peak_bytes:
        return None
    return run.memory_peak_bytes / 2 ** 30
