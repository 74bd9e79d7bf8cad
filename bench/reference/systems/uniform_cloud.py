"""The paper's test system (arXiv:2003.01836 Sec. 4): points uniform in
[-1, 1]^3, charges uniform in [-1, 1], float32."""
from bench.reference import generators


def points(n: int, seed: int, stream: int):
    return generators.uniform_cloud(n, seed, stream)
