"""Share of the (query, key) pairs in the blocks the flash kernels run
that the band lets through: ``flash_pairs_band`` over
``flash_pairs_run``, two arguments of the trainer's host span
``train.step`` (models/transformer.py ``make_train_step``; counted on
the host by ops/flash_attention.py ``block_plan`` from the predicate
the kernels' grid itself asks). ``flash_roofline_pct`` counts the band
only: this is how much of the kernels' score work the mask throws
away. ``note train.step`` has the grid's steps beside it. Layer: train
kernels."""
from chipbench import trace_reduce

SPAN = "train.step"


def step_spans(path: str) -> list[dict]:
    """The arguments of every ``train.step`` host event of a trace."""
    from jax.profiler import ProfileData

    return [
        dict(ev.stats)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name == SPAN
    ]


def read(run):
    if run.summary is None:
        return None
    spans = step_spans(trace_reduce.find_xplane(run.trace_dir))
    spans = [a for a in spans if int(a.get("flash_pairs_run", 0)) > 0]
    if not spans:
        return None
    a = spans[-1]
    print(f"note {SPAN} spans={len(spans)} tokens={a['tokens']} "
          f"flash_block={a['flash_block']} flash_run_steps="
          f"{a['flash_run_steps']} flash_grid_steps={a['flash_grid_steps']}"
          f" flash_pairs_band={a['flash_pairs_band']} flash_pairs_run="
          f"{a['flash_pairs_run']}", flush=True)
    return 100.0 * int(a["flash_pairs_band"]) / int(a["flash_pairs_run"])
