"""ToMe merge (``core/tome.py``): device op time whose ``op_name`` holds the
``tome_merge`` scope, as a share of all device op time in the traced window
(busy time, not the window) (%)."""


def read(run):
    merge_s, op_s = getattr(run, "merge_s", None), getattr(run, "op_s", None)
    if merge_s is None or not op_s:
        return None
    return 100.0 * merge_s / op_s
