"""device.idle_share.hybrid: ``device.idle_share`` of the hybrid cell (it
moves ``iter_s.hybrid``): share of the traced window in which no operation
ran on the device (1 - busy / window)."""


def read(rec):
    tr = rec["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
