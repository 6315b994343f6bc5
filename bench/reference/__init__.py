"""Plain references for the benchmark's correctness checks.

They import nothing of the program under test: the chain and the
solver are written out again here from their published arithmetic, in
float64 with ``scipy.fft`` (``chain.py``, ``ns2d.py``), and
``lowprec.py`` holds the dense DFTs in bf16×3 (``Precision.HIGH``)
arithmetic that the precision control runs in the program's place.
"""
