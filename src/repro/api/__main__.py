"""`python -m repro.api`: run a named study from the registry.

    PYTHONPATH=src python -m repro.api --study edp_array_size --smoke \
        --csv STUDY_edp_array_size.csv

A thin delegate to `repro.api.study._main` — running the package module
(rather than `-m repro.api.study`) avoids runpy re-executing study.py as
`__main__` on top of the copy the package import already registered.
"""
import sys

from ..compile_cache import enable_compile_cache
from .study import _main

enable_compile_cache()
sys.exit(_main())
