"""Empty module, kept only so that ``bench/run_cell.py``'s
``lower_precision`` (which imports every kernel module by name before the
matmul precision is restored) still imports. The sweep has one panel
program, ``repro.ft.online.state``; delete this file together with that import.
"""
