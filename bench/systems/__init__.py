"""The systems a configuration can name (``"system"`` in its file): each
module drives one entry point of the program through a traffic mix and
checks what it returned against ``bench.reference``."""
