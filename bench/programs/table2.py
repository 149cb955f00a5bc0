"""Program library ``table2``: the paper's 16 Table II workloads, from the
benchmark's copy of the program's generator (``gen.program_arrays``),
``cfg["program_blocks"]`` blocks each."""
from bench import gen

NAMES = tuple(gen.TABLE_II)


def arrays(name: str, cfg: dict):
    return gen.program_arrays(name, cfg["program_blocks"])
