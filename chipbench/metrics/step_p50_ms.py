"""Median completion-to-completion interval of the window's steps: the
steadier statistic beside ``train_tok_s``. Layer: trainer."""
import statistics


def read(run):
    iv = run.info.get("intervals")
    return 1e3 * statistics.median(iv) if iv else None
