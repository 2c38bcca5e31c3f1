"""Plain references, one file per architecture, named by the
configuration files."""
