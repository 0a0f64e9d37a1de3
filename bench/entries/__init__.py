"""One module per entry kind; ``harness.py`` loads ``<entry>.py`` by name."""
