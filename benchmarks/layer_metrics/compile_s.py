"""Entry points: seconds jax spent compiling programs, or loading them from
its persistent cache, during set-up (the sum of its own monitoring events:
on a warm cache the loads, 7 s for the 24-layer step)."""


def read(run: dict):
    return run.get("compile_s")
