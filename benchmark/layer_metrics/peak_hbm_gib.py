"""``peak_hbm_gib`` — device: the fullest chip's ``memory_peak_bytes`` as
``run.py`` reports it in ``device`` (live arrays plus the running programs'
reserved scratch, see ``run.peak_bytes``), read when the window has closed
and before the reference runs.  It bounds the batch that fits."""


def read(run):
    peak = run.get("memory_peak_bytes")
    if not peak:
        return None
    return peak / 2.0 ** 30
