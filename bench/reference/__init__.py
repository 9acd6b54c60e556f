"""Plain references that decide ``correct``: a loader and a ResNet step."""
