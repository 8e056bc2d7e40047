"""Runner kind `serve_open`: independent users, arrivals on a schedule in
wall seconds whether or not earlier requests have finished."""
from benchmarks.harness import serving


def run(ctx):
    return serving.run(ctx, "open")
