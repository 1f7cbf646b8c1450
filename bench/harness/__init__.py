"""The chip benchmark's own code: loading cells by name, generating
traffic, driving the front door, reducing traces, and deciding
``correct``.  Nothing here is imported by the program under test."""
