"""Import set-up shared by the scripts in this directory (``run.py``,
``sweep.py``, ``control.py``): each imports this module first."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def setup() -> None:
    """Import the program from ``src`` and this directory as the package
    ``bench``, whose modules (``trace`` among them) must not shadow the
    standard library's from the script's own directory."""
    sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() !=
        ROOT / "bench"]
