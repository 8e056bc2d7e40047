"""Runner kind `serve_closed`: callers that wait, each sending its next
request when its last one ends."""
from benchmarks.harness import serving


def run(ctx):
    return serving.run(ctx, "closed")
