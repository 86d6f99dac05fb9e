"""The whole serving step's share of the chip's int8 peak, in %: useful
operations per image (2·M·K·N summed over the layers' valid shapes) times
the images answered per second in the traced run's window, over the peak
of ``peaks.json``."""

from bench import stats


def read(r):
    if r.peak is None:
        return None
    return stats.mfu_pct(r.ops_per_image, r.images_per_s, r.peak)
