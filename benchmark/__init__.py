"""The cell benchmark: data files plus a harness, outside the program.

``BENCHMARK.json`` at the root of the repo names the cells; everything a
cell needs is a file under this directory that the harness finds by
name (see README.md). Nothing under ``paddle_operator_tpu/`` imports
this package.
"""
