"""The benchmark's tests run on the CPU, at small sizes, with the
compiler's persistent cache off (``tests/conftest.py`` does not reach
this directory)."""
import os

os.environ["REPRO_HLS_CACHE"] = "0"
