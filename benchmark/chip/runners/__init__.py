"""One runner per kind of configuration; chosen by the configuration's `kind`."""
