"""Compile (``tpu/compile_cache.py``): seconds the program spent
lowering, compiling, serializing and loading executables during set-up
(``parser_compile_seconds_total`` at the window's start)."""
from readers import counter_at_start


def read(ctx):
    return counter_at_start(ctx, "parser_compile_seconds_total")
