"""Plain float32 reference of the served dense decoder (see ``model``)."""
