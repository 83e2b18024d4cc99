"""The chip benchmark's own code: the yardstick the program is held to."""
