"""Host clock per training step: window time over steps (entry layer,
`Executor.run` / `ParallelExecutor.run`)."""
LAYER = "entry"
UNIT = "ms"
MOVES = "train_images_per_s"
SOURCE = "host_clock"


def read(run):
    if not run.get("steps"):
        return None
    return run["elapsed_s"] / run["steps"] * 1e3
