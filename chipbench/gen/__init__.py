"""Input generators of the benchmark: copies of the program's generators.

The copies live here so that a change to the program cannot move the
yardstick; ``tests/test_chipbench_gen.py`` shows that each copy still
gives the same arrays as the program's own generator for a fixed seed.
"""
