"""Traffic generators (code) and traffic mixes (``<name>.json`` data)."""
