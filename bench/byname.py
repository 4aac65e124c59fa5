"""Everything a cell is made of is a file found by its name.

A configuration's ``family`` (``bench/families/``), its tokenizer ``kind``
(``bench/tokenizers/``), its warm-up recipe (``bench/warmups/``), its
``reference`` and ``check`` (``bench/references/``, ``bench/checks/``), a
mix's ``generator`` (``bench/generators/``) and a trace metric's ``reducer``
(``bench/reducers/``) each name one ``.py`` file of their directory.  The
harness compares none of these names against a literal: it loads the file
and calls what the file gives.  So a family, tokenizer, role or scorer that
no file here has met is added as new files and edits none.

The file is loaded by its path, under a module name of the benchmark's own:
a name may hold ``-`` or ``.`` (``deberta-v2``), and a directory here may be
called what an installed package is (``tokenizers``).
"""

from __future__ import annotations

import importlib.util
import os
import re

from server import BenchError

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: dict = {}  # path -> module


def path_of(directory: str, name: str) -> str:
    return os.path.join(HERE, directory, name + ".py")


def module(directory: str, name: str):
    """The module ``bench/<directory>/<name>.py``, loaded once."""
    if not isinstance(name, str) or not NAME.match(name):
        raise BenchError(f"not a name: {directory} {name!r}")
    path = path_of(directory, name)
    if path not in _loaded:
        if not os.path.isfile(path):
            raise BenchError(f"no file bench/{directory}/{name}.py")
        key = "bench_" + directory + "__" + re.sub(r"[^A-Za-z0-9_]", "_", name)
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]
