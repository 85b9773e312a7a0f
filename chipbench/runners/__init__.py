"""One runner per kind of cell, found by the configuration's ``kind``."""
