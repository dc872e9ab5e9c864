"""The chip benchmark; ``bench/run.py`` is its command."""
