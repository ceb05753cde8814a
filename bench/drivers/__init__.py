"""One module per traffic driver, named by the traffic file's ``driver``."""
